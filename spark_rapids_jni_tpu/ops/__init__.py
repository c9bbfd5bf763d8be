"""Columnar operator library — the capability surface of the cudf pin.

Each module is the TPU-native equivalent of a cudf kernel family the
reference artifact ships (SURVEY.md §2.3 table): ops lower to XLA where
jnp can express them and to Pallas kernels (kernels/) where it can't.
Data-dependent result sizes (filter/join/groupby) come in two flavors,
mirroring the reference's two-phase 2GB batching discipline
(row_conversion.cu:505-511):

* eager APIs that host-sync the exact size (the cudf/JNI call model), and
* ``*_capped`` jittable variants with caller-fixed capacity + a device
  row count, for whole-query fusion under jit/shard_map.

Two scale disciplines sit above the per-op level (round 4):

* ``*_batches`` forms split giant inputs into fault-sized pieces
  automatically (the join's chunk-probed paths) — the batching the
  reference applies at INT_MAX bytes, applied at TPU limits; and
* the HBM footprint planner (utils/hbm.py) sizes those pieces from a
  per-chip budget instead of constants.
"""

from . import compute, keys
from .binaryop import binary_op, add, sub, mul, div, eq, ne, lt, le, gt, ge
from .unaryop import unary_op, is_null, is_not_null
from .cast import cast
from .reductions import reduce as reduce_column
from .reductions import arg_extreme, extreme_by
from .filter import filter_table, filter_table_capped
from .gather import gather_table, gather_column
from .sort import sort_table, argsort_table, SortKey, is_sorted, merge_sorted
from .hashing import murmur3_column, murmur3_table
from .groupby import groupby_aggregate, GroupbyAgg
from .join import (
    inner_join,
    inner_join_batched,
    inner_join_batches,
    left_join,
    left_join_capped,
    left_join_count,
    membership_mask,
    right_join,
    full_join,
    semi_join,
    anti_join,
)
from .partition import hash_partition, round_robin_partition
from .rounding import round_column
from . import datetime, replace, rounding
from .copying import (
    concatenate,
    concatenate_columns,
    interleave_columns,
    copy_if_else,
    sequence,
    cross_join,
    repeat,
    scatter,
    slice_rows,
    split,
    sample,
)
from .replace import (
    replace_nulls,
    replace_nulls_policy,
    nans_to_nulls,
    find_and_replace,
    clamp,
)
from .search import lower_bound, upper_bound, contains_column
from .scan import scan
from .compaction import distinct, distinct_capped, distinct_count, drop_nulls
from . import window
from .window import (
    rolling_aggregate,
    grouped_rolling_aggregate,
    grouped_range_rolling_aggregate,
    lead,
    lag,
    row_number,
    rank,
    dense_rank,
    percent_rank,
    ntile,
)
from .quantiles import quantile
from . import lists, regex
from .lists import (
    count_elements,
    explode,
    split_explode,
    explode_outer,
    explode_position,
    extract_list_element,
    list_contains,
)
from .regex import (
    contains_re,
    matches_re,
    rlike,
    find_re,
    extract_re,
    replace_re,
    count_re,
)

__all__ = [
    "compute",
    "keys",
    "binary_op",
    "add",
    "sub",
    "mul",
    "div",
    "eq",
    "ne",
    "lt",
    "le",
    "gt",
    "ge",
    "unary_op",
    "is_null",
    "is_not_null",
    "cast",
    "reduce_column",
    "arg_extreme",
    "extreme_by",
    "filter_table",
    "filter_table_capped",
    "gather_table",
    "gather_column",
    "sort_table",
    "argsort_table",
    "SortKey",
    "is_sorted",
    "merge_sorted",
    "murmur3_column",
    "murmur3_table",
    "groupby_aggregate",
    "inner_join_batches",
    "GroupbyAgg",
    "inner_join",
    "inner_join_batched",
    "left_join",
    "left_join_capped",
    "left_join_count",
    "membership_mask",
    "right_join",
    "full_join",
    "semi_join",
    "anti_join",
    "hash_partition",
    "round_robin_partition",
    "round_column",
    "datetime",
    "concatenate",
    "concatenate_columns",
    "interleave_columns",
    "copy_if_else",
    "sequence",
    "cross_join",
    "repeat",
    "scatter",
    "slice_rows",
    "split",
    "sample",
    "replace_nulls",
    "replace_nulls_policy",
    "nans_to_nulls",
    "find_and_replace",
    "clamp",
    "lower_bound",
    "upper_bound",
    "contains_column",
    "scan",
    "distinct",
    "distinct_capped",
    "distinct_count",
    "drop_nulls",
    "window",
    "rolling_aggregate",
    "grouped_rolling_aggregate",
    "grouped_range_rolling_aggregate",
    "lead",
    "lag",
    "row_number",
    "rank",
    "dense_rank",
    "percent_rank",
    "ntile",
    "quantile",
    "lists",
    "count_elements",
    "explode",
    "split_explode",
    "explode_outer",
    "explode_position",
    "extract_list_element",
    "list_contains",
    "regex",
    "contains_re",
    "matches_re",
    "rlike",
    "find_re",
    "extract_re",
    "replace_re",
    "count_re",
]
