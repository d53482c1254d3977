"""The yardstick: everything a run of one cell needs that is not the system
under test. ``system.py`` drives ``flink_ml_tpu``; ``program_spans.py`` and
``cold_spans.py`` read its tracer's spans and nothing else of it."""
