"""Aggregate function expressions (reference `AggregateFunctions.scala`: GpuSum,
GpuCount, GpuMin, GpuMax, GpuAverage, GpuFirst, GpuLast...).

Like the reference, each aggregate declares its partial (update) and final (merge)
semantics; the hash-aggregate exec lowers them to sort-based segmented reductions on
device (ops/segmented.py). `Sum` on integrals widens to LONG; `Average` carries a
(sum, count) pair through the partial phase — the same buffer layout the reference
uses for its partial aggregates."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from .. import types as T
from .base import Expression

__all__ = ["AggregateFunction", "Sum", "Count", "Min", "Max", "Average", "First",
           "Last", "CountDistinct", "VariancePop", "VarianceSamp",
           "StddevPop", "StddevSamp", "CollectList", "CollectSet",
           "ApproximatePercentile"]


class AggregateFunction(Expression):
    """Declarative aggregate: the exec consumes these descriptors."""

    # data-dependent output fanout: the exec must run its single-pass path
    single_pass = False

    # segmented-reduce op names used in the update phase, one per partial buffer
    update_ops: List[str] = []
    # ops merging partial buffers across batches/partitions
    merge_ops: List[str] = []

    def __init__(self, child: Optional[Expression] = None):
        super().__init__([] if child is None else [child])

    @property
    def child(self) -> Optional[Expression]:
        return self.children[0] if self.children else None

    # types of the partial aggregation buffers
    def partial_types(self) -> List[T.DataType]:
        raise NotImplementedError

    # produce the final value from partial buffers (array-level, xp-generic)
    def evaluate_final(self, xp, partials, counts):
        raise NotImplementedError

    @property
    def nullable(self):
        return True


class Sum(AggregateFunction):
    update_ops = ["sum"]
    merge_ops = ["sum"]

    @property
    def data_type(self):
        ct = self.child.data_type
        if T.is_integral(ct):
            return T.LONG
        if isinstance(ct, T.DecimalType):
            return T.DecimalType.bounded(ct.precision + 10, ct.scale)
        return T.DOUBLE

    def partial_types(self):
        return [self.data_type]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class Count(AggregateFunction):
    """count(expr) or count(*) (child None)."""
    update_ops = ["count"]
    merge_ops = ["sum"]

    @property
    def data_type(self):
        return T.LONG

    @property
    def nullable(self):
        return False

    def partial_types(self):
        return [T.LONG]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class Min(AggregateFunction):
    update_ops = ["min"]
    merge_ops = ["min"]

    @property
    def data_type(self):
        return self.child.data_type

    def partial_types(self):
        return [self.data_type]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class Max(AggregateFunction):
    update_ops = ["max"]
    merge_ops = ["max"]

    @property
    def data_type(self):
        return self.child.data_type

    def partial_types(self):
        return [self.data_type]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class Average(AggregateFunction):
    update_ops = ["sum", "count"]
    merge_ops = ["sum", "sum"]

    @property
    def data_type(self):
        ct = self.child.data_type
        if isinstance(ct, T.DecimalType):
            return T.DecimalType.bounded(ct.precision + 4, ct.scale + 4)
        return T.DOUBLE

    @property
    def sum_type(self):
        """The sum buffer: Spark's decimal(p + 10, s) for a decimal child
        (exact; the final step divides it by the count), else DOUBLE."""
        ct = self.child.data_type
        if isinstance(ct, T.DecimalType):
            return T.DecimalType.bounded(ct.precision + 10, ct.scale)
        return T.DOUBLE

    def partial_types(self):
        return [self.sum_type, T.LONG]

    def evaluate_final(self, xp, partials, counts):
        s, c = partials
        return xp.where(c > 0, s / xp.maximum(c, 1), np.float64(0.0))


class First(AggregateFunction):
    def __init__(self, child, ignore_nulls: bool = False):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    update_ops = ["first"]
    merge_ops = ["first"]

    def __repr__(self):
        # ignore_nulls changes the traced program, so it must be visible to
        # repr-derived compile-cache keys (compile/service.py)
        extra = ", ignore_nulls" if self.ignore_nulls else ""
        return f"{self.name}({self.children[0]!r}{extra})"

    @property
    def data_type(self):
        return self.child.data_type

    def partial_types(self):
        return [self.data_type]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class Last(First):
    update_ops = ["last"]
    merge_ops = ["last"]


class CountDistinct(AggregateFunction):
    """count(distinct x): planner rewrites into dedup + count (reference handles via
    Spark's two-phase distinct rewrite); marked here for the API surface."""
    update_ops = ["count_distinct"]
    merge_ops = ["sum"]

    @property
    def data_type(self):
        return T.LONG

    def partial_types(self):
        return [T.LONG]

    def evaluate_final(self, xp, partials, counts):
        return partials[0]


class _VarianceFamily(AggregateFunction):
    """var_pop/var_samp/stddev_pop/stddev_samp via (sum, sum-of-squares,
    count) partials (reference AggregateFunctions.scala CentralMomentAgg —
    the reference carries (n, avg, m2); the moment form here merges by plain
    sums, which the differential harness compares approximately)."""
    update_ops = ["sum", "sumsq", "count"]
    merge_ops = ["sum", "sum", "sum"]
    sample = False
    sqrt = False

    @property
    def data_type(self):
        return T.DOUBLE

    def partial_types(self):
        return [T.DOUBLE, T.DOUBLE, T.LONG]


class VariancePop(_VarianceFamily):
    pass


class VarianceSamp(_VarianceFamily):
    sample = True


class StddevPop(_VarianceFamily):
    sqrt = True


class StddevSamp(_VarianceFamily):
    sample = True
    sqrt = True


class CollectList(AggregateFunction):
    """collect_list: gathers non-null values per group into an array.
    Single-pass only (the output fanout is data-dependent, so the exec runs
    a dedicated two-phase kernel over the concatenated input)."""
    single_pass = True

    @property
    def data_type(self):
        return T.ArrayType(self.child.data_type)

    def partial_types(self):
        return [self.data_type]


class CollectSet(CollectList):
    """collect_set: distinct non-null values per group."""


class ApproximatePercentile(AggregateFunction):
    """approx_percentile(col, percentage[, accuracy]): nearest-rank element
    selection over the group-sorted values (an exact percentile — a valid
    refinement of the reference's t-digest approximation; both engines use
    the same rank rule round(q * (n-1)))."""
    single_pass = True

    def __init__(self, child, percentages, accuracy: int = 10000):
        super().__init__(child)
        self.scalar = not isinstance(percentages, (list, tuple))
        self.percentages = [percentages] if self.scalar else list(percentages)
        self.accuracy = accuracy

    def __repr__(self):
        # percentages select output ranks inside the traced kernel: keep
        # them in repr so compile-cache keys can't alias two configurations
        return (f"{self.name}({self.children[0]!r}, "
                f"{self.percentages}, {self.accuracy})")

    @property
    def data_type(self):
        return T.DOUBLE if self.scalar else T.ArrayType(T.DOUBLE)

    def partial_types(self):
        return [self.data_type]


class CountIf(AggregateFunction):
    """count_if(predicate): rows where the predicate is true."""

    @property
    def data_type(self):
        return T.LONG

    def partial_types(self):
        return [T.LONG]


class BoolAnd(AggregateFunction):
    """bool_and / every."""

    @property
    def data_type(self):
        return T.BOOLEAN

    def partial_types(self):
        return [T.BOOLEAN]


class BoolOr(AggregateFunction):
    """bool_or / any / some."""

    @property
    def data_type(self):
        return T.BOOLEAN

    def partial_types(self):
        return [T.BOOLEAN]


class _BitAgg(AggregateFunction):
    """bit_and/bit_or/bit_xor over integral inputs."""

    op = "and"

    @property
    def data_type(self):
        return self.child.data_type

    def partial_types(self):
        return [self.data_type]


class BitAndAgg(_BitAgg):
    op = "and"


class BitOrAgg(_BitAgg):
    op = "or"


class BitXorAgg(_BitAgg):
    op = "xor"


class _MomentFamily(AggregateFunction):
    """skewness / kurtosis via raw power sums s1..s4 + count partials."""

    @property
    def data_type(self):
        return T.DOUBLE

    def partial_types(self):
        return [T.DOUBLE, T.DOUBLE, T.DOUBLE, T.DOUBLE, T.LONG]


class Skewness(_MomentFamily):
    pass


class Kurtosis(_MomentFamily):
    pass
