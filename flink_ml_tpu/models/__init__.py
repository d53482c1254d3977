"""Algorithm library (ref: flink-ml-lib, SURVEY.md §2.4).

Areas mirror the reference package layout: classification, clustering,
regression, feature, recommendation, evaluation, stats.
"""

from flink_ml_tpu._cold import importing as _importing

# the cold span ``import:flink_ml_tpu.models`` (docs/observability.md):
# every model of the library, whichever one the process came for
with _importing("flink_ml_tpu.models"):
    # clustering first: models.online depends on clustering.kmeans, and
    # both classification and clustering re-export from models.online
    from flink_ml_tpu.models import clustering  # noqa: F401
    from flink_ml_tpu.models import classification  # noqa: F401
    from flink_ml_tpu.models import evaluation  # noqa: F401
    from flink_ml_tpu.models import feature  # noqa: F401
    from flink_ml_tpu.models import recommendation  # noqa: F401
    from flink_ml_tpu.models import regression  # noqa: F401
    from flink_ml_tpu.models import stats  # noqa: F401
