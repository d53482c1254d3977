"""Feature-label statistical tests as AlgoOperators.

Ref parity: flink-ml-lib stats/{chisqtest/ChiSqTest.java,
anovatest/ANOVATest.java, fvaluetest/FValueTest.java} — all share
(featuresCol, labelCol, flatten): flatten=false emits a single row
("pValues" vector, "degreesOfFreedom", "statistics"); flatten=true emits
one row per feature ("featureIndex", "pValue", "degreeOfFreedom",
"statistic"). Numeric cores live in flink_ml_tpu.ops.stats.
"""

from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

from flink_ml_tpu.api.stage import AlgoOperator
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.ops.stats import anova_f_test, chi_square_test, f_value_test
from flink_ml_tpu.params.shared import HasFeaturesCol, HasFlatten, HasLabelCol


class _StatTestBase(AlgoOperator, HasFeaturesCol, HasLabelCol, HasFlatten):
    _test: Callable = None
    #: the test reads a device-resident table where it lies
    _on_device = False

    def transform(self, table: Table) -> Tuple[Table]:
        if self._on_device:
            from flink_ml_tpu.ops import columnar

            x, _ = columnar.fit_vectors(table, self.features_col)
            y = table.column(self.label_col)
        else:
            x = table.vectors(self.features_col, np.float64)
            y = np.asarray(table.column(self.label_col))
        statistics, p_values, dofs = type(self)._test(x, y)
        if self.flatten:
            d = len(p_values)
            return (Table.from_columns(
                featureIndex=np.arange(d, dtype=np.int64),
                pValue=p_values.astype(np.float64),
                degreeOfFreedom=dofs.astype(np.int64),
                statistic=statistics.astype(np.float64)),)
        return (Table.from_columns(
            pValues=as_dense_vector_column(p_values[None, :]),
            degreesOfFreedom=[dofs.astype(np.int64)],
            statistics=as_dense_vector_column(statistics[None, :])),)


class ChiSqTest(_StatTestBase):
    """Pearson chi-squared independence test (ref: ChiSqTest.java:79)."""
    _test = staticmethod(chi_square_test)


class ANOVATest(_StatTestBase):
    """One-way ANOVA F-test (ref: ANOVATest.java). A device-resident
    table is read once on the device (``ops/stats.moments_on_device``): the
    grouped moments cross to the host, the table does not."""
    _test = staticmethod(anova_f_test)
    _on_device = True


class FValueTest(_StatTestBase):
    """Univariate regression F-test (ref: FValueTest.java)."""
    _test = staticmethod(f_value_test)
