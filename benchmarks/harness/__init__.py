"""The yardstick: everything a run of one cell needs that is not the system
under test. Only ``system.py`` imports ``flink_ml_tpu``."""
