"""TpuOverrides: the plan-rewrite pass — the heart of the framework.

Faithful architectural port of the reference's L5 layer (it is Spark-facing
logic, not CUDA): ``GpuOverrides`` wraps the physical plan in a metadata tree,
tags every node with "cannot replace because ..." reasons, renders explain
output, converts eligible subtrees, and a post-pass inserts transitions
(reference: GpuOverrides.scala:1790-1806 apply; RapidsMeta.scala:65,186-213
tagging; GpuTransitionOverrides.scala:36 transitions; per-op conf keys
GpuOverrides.scala:126-131; explain rendering RapidsMeta.scala:224-250).

Differences are TPU-native by design: the replacement execs run XLA programs,
transitions are host<->HBM uploads rather than row<->columnar conversions
(our CPU path is already columnar Arrow), and coalescing goals are capacity
buckets."""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Type

from .. import types as T
from ..config import (TpuConf, EXPLAIN, HAS_NANS, REPLACE_SORT_MERGE_JOIN,
                      SQL_ENABLED, TEST_ENABLED, VARIABLE_FLOAT_AGG)
from ..exec import execs as E
from ..ops import aggregates as AGG
from ..ops import arithmetic as ARITH
from ..ops import bitwise as BIT
from ..ops import conditional as COND
from ..ops import datetime as DT
from ..ops import math as MATH
from ..ops import predicates as PRED
from ..ops import strings as STR
from ..ops.cast import Cast
from ..ops.expression import (Alias, AttributeReference, BoundReference,
                              Expression, Literal)
from . import physical as P


# ---------------------------------------------------------------------------
# Expression rules (the ExprRule registry, GpuOverrides.scala:1496)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExprRule:
    name: str
    incompat: bool = False
    disabled: bool = False
    #: extra check: returns a reason string or None
    tag: Optional[Callable[[Expression, TpuConf], Optional[str]]] = None


EXPR_RULES: Dict[Type[Expression], ExprRule] = {}


def _expr(cls, name=None, incompat=False, disabled=False, tag=None):
    EXPR_RULES[cls] = ExprRule(name or cls.__name__, incompat, disabled, tag)


def _cast_tag(e: Expression, conf: TpuConf) -> Optional[str]:
    """Conf gates on the inexact cast paths (reference GpuCast checks via
    RapidsConf.scala:395-425)."""
    from ..config import CAST_STRING_TO_FLOAT, CAST_STRING_TO_TIMESTAMP
    src = e.child.data_type
    to = e.to
    if src is T.STRING and to.name in ("float", "double") \
            and not conf.get(CAST_STRING_TO_FLOAT):
        return ("string->float cast differs on edge cases; set "
                "spark.rapids.sql.castStringToFloat.enabled=true")
    if src is T.STRING and to is T.TIMESTAMP \
            and not conf.get(CAST_STRING_TO_TIMESTAMP):
        return ("string->timestamp cast supports fixed formats only; set "
                "spark.rapids.sql.castStringToTimestamp.enabled=true")
    if src.name in ("float", "double") and to is T.STRING:
        # Java shortest-roundtrip float formatting has no device kernel.
        return ("float->string cast is not supported on the device "
                "(reference gates it behind castFloatToString)")
    return None


for _cls in [AttributeReference, BoundReference, Literal, Alias]:
    _expr(_cls)
_expr(Cast, tag=_cast_tag)
for _cls in [ARITH.Add, ARITH.Subtract, ARITH.Multiply, ARITH.Divide,
             ARITH.IntegralDivide, ARITH.Remainder, ARITH.Pmod,
             ARITH.UnaryMinus, ARITH.Abs]:
    _expr(_cls)
for _cls in [PRED.EqualTo, PRED.NotEqual, PRED.LessThan, PRED.LessThanOrEqual,
             PRED.GreaterThan, PRED.GreaterThanOrEqual, PRED.EqualNullSafe,
             PRED.And, PRED.Or, PRED.Not, PRED.IsNull, PRED.IsNotNull,
             PRED.IsNaN]:
    _expr(_cls)
_expr(PRED.In)
for _cls in [MATH.Sin, MATH.Cos, MATH.Tan, MATH.Asin, MATH.Acos, MATH.Atan,
             MATH.Sinh, MATH.Cosh, MATH.Tanh, MATH.Exp, MATH.Expm1, MATH.Log,
             MATH.Log2, MATH.Log10, MATH.Log1p, MATH.Sqrt, MATH.Cbrt,
             MATH.Rint, MATH.Signum, MATH.ToDegrees, MATH.ToRadians,
             MATH.Floor, MATH.Ceil, MATH.Pow, MATH.Atan2]:
    _expr(_cls)
_expr(COND.If)
_expr(COND.CaseWhen)
_expr(COND.Coalesce)
_expr(COND.NaNvl)
for _cls in [AGG.Min, AGG.Max, AGG.Sum, AGG.Count, AGG.Average, AGG.First,
             AGG.Last]:
    _expr(_cls)


def _like_tag(e: "STR.Like", conf: TpuConf) -> Optional[str]:
    # General %/_ patterns run the device wildcard DP (W x P unrolled
    # vector ops); pathologically long patterns would bloat the compiled
    # program, so they keep the CPU path.
    if len(e.tokens()) > 48:
        return "LIKE pattern longer than 48 tokens runs on CPU (compiled " \
               "wildcard-DP program size)"
    return None


def _substring_tag(e: "STR.Substring", conf: TpuConf) -> Optional[str]:
    if not isinstance(e.children[1], Literal) or \
            not isinstance(e.children[2], Literal):
        return "substring with non-literal pos/len is not supported on device"
    return None


for _cls in [STR.Length, STR.Upper, STR.Lower, STR.StartsWith, STR.EndsWith,
             STR.Contains, STR.ConcatStrings, STR.StringTrim,
             STR.StringTrimLeft, STR.StringTrimRight]:
    _expr(_cls)
_expr(STR.Like, tag=_like_tag)
_expr(STR.Substring, tag=_substring_tag)
for _cls in [DT.Year, DT.Month, DT.DayOfMonth, DT.Quarter, DT.DayOfYear,
             DT.DayOfWeek, DT.WeekDay, DT.Hour, DT.Minute, DT.Second,
             DT.LastDay, DT.DateAdd, DT.DateSub, DT.DateDiff]:
    _expr(_cls)
for _cls in [BIT.BitwiseAnd, BIT.BitwiseOr, BIT.BitwiseXor, BIT.BitwiseNot,
             BIT.ShiftLeft, BIT.ShiftRight, BIT.ShiftRightUnsigned]:
    _expr(_cls)

from ..ops import nondeterministic as ND  # noqa: E402
from ..ops import strings2 as STR2  # noqa: E402

for _cls in [STR2.StringReplace, STR2.LPad, STR2.RPad, STR2.StringLocate,
             STR2.InitCap, STR2.SubstringIndex, STR2.Reverse,
             STR2.StringRepeat]:
    _expr(_cls)


def _regexp_tag(e: "STR2.RegExpReplace", conf: TpuConf) -> Optional[str]:
    if not e.is_literal_pattern:
        return ("regexp_replace with regex metacharacters runs on CPU "
                "(the reference lowers only literal patterns, "
                "GpuStringReplace rule)")
    return None


_expr(STR2.RegExpReplace, tag=_regexp_tag)
for _cls in [ND.Rand, ND.SparkPartitionID, ND.MonotonicallyIncreasingID]:
    _expr(_cls)
_expr(PRED.AtLeastNNonNulls)


def _string_split_tag(e, conf: TpuConf) -> Optional[str]:
    return ("ARRAY<STRING> has no device layout; split(str, delim) "
            "evaluates on the host path (reference GpuStringSplit gates "
            "to literal patterns, stringFunctions.scala:862)")


_expr(STR2.StringSplit, tag=_string_split_tag)


def _input_file_tag(e, conf: TpuConf) -> Optional[str]:
    # Normally rewritten into hidden scan metadata columns before planning
    # (plan/input_file.py); one surviving here sits at a site the rewrite
    # does not cover (aggregate/join/sort expressions).
    return ("input_file expressions are only supported in projections and "
            "filters (rewritten to scan metadata columns)")


for _cls in [ND.InputFileName, ND.InputFileBlockStart,
             ND.InputFileBlockLength]:
    _expr(_cls, tag=_input_file_tag)


def _unix_ts_tag(e, conf: TpuConf) -> Optional[str]:
    if not e.is_supported_format:
        return (f"timestamp pattern {e.fmt!r} is outside the fixed-width "
                "yyyy/MM/dd[/HH/mm/ss] family the device parses "
                "(reference fixed-format stance)")
    return None


_expr(DT.UnixTimestamp, tag=_unix_ts_tag)
_expr(DT.FromUnixTime, tag=_unix_ts_tag)

from ..ops import complex as CPX  # noqa: E402


def _get_array_item_tag(e: "CPX.GetArrayItem", conf: TpuConf) \
        -> Optional[str]:
    if not isinstance(e.children[1], Literal):
        return ("GetArrayItem with a non-literal ordinal is not supported "
                "(reference complexTypeExtractors.scala limits to literal "
                "ordinals)")
    return None


_expr(CPX.CreateArray)
_expr(CPX.GetArrayItem, tag=_get_array_item_tag)
_expr(CPX.Size)
_expr(CPX.ArrayContains)
_expr(CPX.CreateNamedStruct)
_expr(CPX.GetStructField)

# Compiled-UDF loop IR (udf-compiler CFG output; lax.while_loop on device).
# PythonUDF — the uncompilable fallback — deliberately has NO rule, so
# plans containing it keep their operator on the CPU with a reason.
from ..udf.loops import (LoopExpr as _LoopExpr,  # noqa: E402
                         LoopVar as _LoopVar, NullPropIf as _NullPropIf,
                         TypedIf as _TypedIf)

_expr(_LoopExpr)
_expr(_LoopVar)
_expr(_TypedIf)
_expr(_NullPropIf)


# ---------------------------------------------------------------------------
# Meta tree (RapidsMeta analog)
# ---------------------------------------------------------------------------


class ExecMeta:
    """Wrapper of one physical node recording replaceability."""

    def __init__(self, node: P.PhysicalPlan, rule: "ExecRule",
                 children: List["ExecMeta"]):
        self.node = node
        self.rule = rule
        self.children = children
        self.reasons: List[str] = []

    def will_not_work(self, reason: str):
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_replace(self) -> bool:
        return not self.reasons

    # -- tagging ------------------------------------------------------------
    def tag(self, conf: TpuConf):
        for c in self.children:
            c.tag(conf)
        if self.rule is None:
            self.will_not_work(
                f"no TPU replacement rule for {self.node.node_name()}")
            return
        key = TpuConf.operator_conf_key("exec", self.rule.name)
        if not conf.is_operator_enabled(key, self.rule.incompat,
                                        self.rule.disabled):
            self.will_not_work(f"{key} is disabled")
        # Every input column must be device-representable: if the child
        # ends up host-side, its whole output schema crosses the upload
        # boundary (areAllSupportedTypes applied to plan inputs — the
        # reference tags on input schemas the same way,
        # RapidsMeta.tagForGpu:186-213).
        for child in self.node.children:
            for f in child.schema:
                if not T.device_supported(f.data_type):
                    self.will_not_work(
                        f"input column {f.name}: type {f.data_type} is "
                        "not supported on TPU")
        for expr in self.rule.exprs_of(self.node):
            self._tag_expr(expr, conf)
        if self.rule.tag is not None:
            self.rule.tag(self, conf)

    def _tag_expr(self, expr: Expression, conf: TpuConf):
        rule = EXPR_RULES.get(type(expr))
        if rule is None:
            self.will_not_work(
                f"expression {type(expr).__name__} is not supported on TPU")
        else:
            key = TpuConf.operator_conf_key("expression", rule.name)
            if not conf.is_operator_enabled(key, rule.incompat, rule.disabled):
                self.will_not_work(f"{key} is disabled")
            if rule.tag is not None:
                reason = rule.tag(expr, conf)
                if reason:
                    self.will_not_work(reason)
            try:
                dt = expr.data_type
                if not T.device_supported(dt):
                    self.will_not_work(f"type {dt} is not supported on TPU")
            except (RuntimeError, NotImplementedError):
                pass
        for c in expr.children:
            self._tag_expr(c, conf)

    # -- conversion ---------------------------------------------------------
    def convert(self, conf: TpuConf) -> P.PhysicalPlan:
        new_children = [c.convert(conf) for c in self.children]
        if self.can_replace and self.rule is not None:
            return self.rule.convert(self.node, new_children, conf)
        if list(new_children) != list(self.node.children):
            return self.node.with_children(new_children)
        return self.node

    # -- explain (RapidsMeta.explain analog) --------------------------------
    def explain(self, all_nodes: bool, indent: int = 0) -> str:
        marker = "*" if self.can_replace else "!"
        line = ""
        if all_nodes or not self.can_replace:
            reason = ("" if self.can_replace
                      else " cannot run on TPU because " + "; ".join(self.reasons))
            line = ("  " * indent + f"{marker} {self.node.node_name()}"
                    + reason + "\n")
        for c in self.children:
            line += c.explain(all_nodes, indent + 1)
        return line


@dataclasses.dataclass
class ExecRule:
    """Replacement rule for one Cpu exec class (ExecRule analog,
    GpuOverrides.scala:236)."""

    name: str
    exprs_of: Callable[[P.PhysicalPlan], List[Expression]]
    convert: Callable[[P.PhysicalPlan, List[P.PhysicalPlan], TpuConf],
                      P.PhysicalPlan]
    tag: Optional[Callable[[ExecMeta, TpuConf], None]] = None
    incompat: bool = False
    disabled: bool = False


def _agg_exprs(node: P.CpuHashAggregateExec) -> List[Expression]:
    out = list(node.groupings)
    for a in node.aggregates:
        out.append(a.func)
    return out


def _no_complex_keys(meta: ExecMeta, exprs, what: str):
    for e in exprs:
        if isinstance(e.data_type, (T.ArrayType, T.StructType)):
            meta.will_not_work(
                f"{what} of type {e.data_type} is not supported on TPU")


def _agg_tag(meta: ExecMeta, conf: TpuConf):
    node: P.CpuHashAggregateExec = meta.node
    _no_complex_keys(meta, node.groupings, "grouping key")
    if not conf.get(VARIABLE_FLOAT_AGG):
        for a in node.aggregates:
            if isinstance(a.func, (AGG.Sum, AGG.Average)) and a.func.child \
                    is not None and a.func.child.data_type.is_floating:
                meta.will_not_work(
                    "float sum/average can differ from CPU due to reduction "
                    "order; set spark.rapids.sql.variableFloatAgg.enabled=true")


def _window_exprs(node: "P.CpuWindowExec") -> List[Expression]:
    from ..ops import windows as W
    out: List[Expression] = []
    for _, we in node.window_exprs:
        out.extend(we.func.children)
        out.extend(we.spec.partition_by)
        out.extend(o.child for o in we.spec.order_by)
    return out


def _window_tag(meta: ExecMeta, conf: TpuConf):
    """Gating mirrors GpuWindowExpression.tag: supported functions, literal
    frame bounds, range frames need one orderable order-by key."""
    from ..ops import windows as W
    node = meta.node
    for name, we in node.window_exprs:
        f = we.func
        if not isinstance(f, W.WINDOW_AGG_TYPES + W.RANKING_TYPES):
            meta.will_not_work(
                f"window function {type(f).__name__} is not supported on TPU")
            continue
        if isinstance(f, (AGG.Sum, AGG.Average)) and f.children and \
                f.children[0].data_type.is_floating and \
                not conf.get(VARIABLE_FLOAT_AGG):
            meta.will_not_work(
                "windowed float sum/average can differ from CPU due to "
                "reduction order; set "
                "spark.rapids.sql.variableFloatAgg.enabled=true")
        frame = we.spec.effective_frame()
        if frame.frame_type == "range" and not isinstance(f, W.RANKING_TYPES):
            has_offset = frame.lower.kind == "offset" or \
                frame.upper.kind == "offset"
            if has_offset:
                if len(we.spec.order_by) != 1:
                    meta.will_not_work("range frames with offsets require "
                                       "exactly one order-by key")
                else:
                    okt = we.spec.order_by[0].child.data_type
                    if okt in (T.STRING, T.BOOLEAN) or okt is T.NULL:
                        meta.will_not_work(
                            f"range frame offsets on {okt} order-by are not "
                            "supported (reference limits range frames to "
                            "timestamp order-by, GpuWindowExec.scala:92)")
        for e in we.spec.partition_by:
            if e.data_type not in T.DEFAULT_DEVICE_TYPES:
                meta.will_not_work(
                    f"partition key type {e.data_type} not supported")
        _no_complex_keys(meta, [o.child for o in we.spec.order_by],
                         "window order-by key")


def _join_tag(meta: ExecMeta, conf: TpuConf):
    """Join-type / condition gating (GpuHashJoin.tagJoin analog,
    GpuHashJoin.scala:29: conditions only for inner joins)."""
    node: P.CpuJoinExec = meta.node
    if not node.left_keys:
        meta.will_not_work("hash join requires equi keys")
    _no_complex_keys(meta, list(node.left_keys) + list(node.right_keys),
                     "join key")
    if node.condition is not None and node.join_type != "inner":
        meta.will_not_work(
            f"conditions are not supported for {node.join_type} joins "
            "(reference limits join conditions to inner joins)")
    if type(node) is P.CpuJoinExec \
            and not conf.get(REPLACE_SORT_MERGE_JOIN):
        meta.will_not_work(
            "spark.rapids.sql.replaceSortMergeJoin.enabled=false keeps "
            "sort-merge-shaped (non-broadcast) equi joins on the CPU "
            "(reference GpuSortMergeJoinMeta, RapidsConf.scala:384)")


def _nlj_tag(meta: ExecMeta, conf: TpuConf):
    node: P.CpuNestedLoopJoinExec = meta.node
    if node.join_type not in ("cross", "inner", "left", "left_semi",
                              "left_anti"):
        meta.will_not_work(f"nested-loop {node.join_type} join is not "
                           "supported on TPU")


EXEC_RULES: Dict[Type[P.PhysicalPlan], ExecRule] = {
    P.CpuProjectExec: ExecRule(
        "Project",
        lambda n: n.exprs,
        lambda n, ch, conf: E.TpuProjectExec(ch[0], n.exprs)),
    P.CpuFilterExec: ExecRule(
        "Filter",
        lambda n: [n.condition],
        lambda n, ch, conf: E.TpuFilterExec(ch[0], n.condition)),
    P.CpuHashAggregateExec: ExecRule(
        "HashAggregate",
        _agg_exprs,
        lambda n, ch, conf: E.TpuHashAggregateExec(ch[0], n.groupings,
                                                   n.aggregates),
        tag=_agg_tag),
    P.CpuJoinExec: ExecRule(
        "ShuffledHashJoin",
        lambda n: list(n.left_keys) + list(n.right_keys)
        + ([n.condition] if n.condition is not None else []),
        lambda n, ch, conf: E.TpuShuffledHashJoinExec(
            ch[0], ch[1], n.join_type, n.left_keys, n.right_keys, n.schema,
            n.condition),
        tag=_join_tag),
    P.CpuBroadcastHashJoinExec: ExecRule(
        "BroadcastHashJoin",
        lambda n: list(n.left_keys) + list(n.right_keys)
        + ([n.condition] if n.condition is not None else []),
        lambda n, ch, conf: _make_broadcast_join(n, ch),
        tag=_join_tag),
    P.CpuNestedLoopJoinExec: ExecRule(
        "BroadcastNestedLoopJoin",
        lambda n: [n.condition] if n.condition is not None else [],
        lambda n, ch, conf: _make_nlj(n, ch),
        tag=_nlj_tag),
    P.CpuSortExec: ExecRule(
        "Sort",
        lambda n: [o.child for o in n.orders],
        lambda n, ch, conf: E.TpuSortExec(ch[0], n.orders),
        tag=lambda m, conf: _no_complex_keys(
            m, [o.child for o in m.node.orders], "sort key")),
    P.CpuLimitExec: ExecRule(
        "GlobalLimit",
        lambda n: [],
        lambda n, ch, conf: _make_global_limit(n, ch, conf)),
    P.CpuLocalLimitExec: ExecRule(
        "LocalLimit",
        lambda n: [],
        lambda n, ch, conf: E.TpuLocalLimitExec(ch[0], n.n)),
    P.CpuUnionExec: ExecRule(
        "Union",
        lambda n: [],
        lambda n, ch, conf: E.TpuUnionExec(ch, n.schema)),
    P.CpuExpandExec: ExecRule(
        "Expand",
        lambda n: [e for proj in n.projections for e in proj],
        lambda n, ch, conf: E.TpuExpandExec(ch[0], n.projections, n.schema)),
    P.CpuGenerateExec: ExecRule(
        "Generate",
        lambda n: [n.generator],
        lambda n, ch, conf: E.TpuGenerateExec(ch[0], n.generator, n.outer,
                                              n.pos, n.schema)),
    P.CpuRangeExec: ExecRule(
        "Range",
        lambda n: [],
        lambda n, ch, conf: E.TpuRangeExec(n.start, n.end, n.step)),
    P.CpuWindowExec: ExecRule(
        "Window",
        _window_exprs,
        lambda n, ch, conf: _make_window(n, ch),
        tag=_window_tag),
}


def _make_window(n: "P.CpuWindowExec", ch):
    from ..exec.window_exec import TpuWindowExec
    return TpuWindowExec(ch[0], n.window_exprs, n.schema)


def _make_global_limit(n: "P.CpuLimitExec", ch, conf):
    """GlobalLimit over a device sort collapses LocalLimit+Sort into the
    top-k exec (limit-into-sort; the reference's cudf partial-sort
    analog) when n is small enough that top-k beats a global sort."""
    from ..config import TOPK_THRESHOLD
    inner = ch[0]
    if (0 < n.n <= conf.get(TOPK_THRESHOLD)
            and isinstance(inner, E.TpuLocalLimitExec)
            and isinstance(inner.children[0], E.TpuSortExec)):
        sort = inner.children[0]
        return E.TpuTopKExec(sort.children[0], sort.orders, n.n)
    return E.TpuLimitExec(ch[0], n.n)


def _make_broadcast_join(n: "P.CpuBroadcastHashJoinExec", ch):
    from ..exec.joins import (TpuBroadcastExchangeExec,
                              TpuBroadcastHashJoinExec)
    return TpuBroadcastHashJoinExec(
        ch[0], TpuBroadcastExchangeExec(ch[1]), n.join_type, n.left_keys,
        n.right_keys, n.schema, n.condition)


def _shuffle_tag(meta: ExecMeta, conf: TpuConf):
    factory = meta.node.partitioner_factory
    if factory.mode == "range":
        # String keys range-partition on device via the byte-lexicographic
        # bound comparison (GpuRangePartitioner.scala:237 parity).
        _no_complex_keys(meta, [o.child for o in (factory.orders or [])],
                         "range partitioning key")


def _register_shuffle_rule():
    from ..shuffle.exchange import (CpuShuffleExchangeExec,
                                    TpuShuffleExchangeExec)
    EXEC_RULES[CpuShuffleExchangeExec] = ExecRule(
        "ShuffleExchange",
        lambda n: list(n.partitioner_factory.keys or [])
        + [o.child for o in (n.partitioner_factory.orders or [])],
        lambda n, ch, conf: TpuShuffleExchangeExec(
            ch[0], n.partitioner_factory, n.n_parts),
        tag=_shuffle_tag)


_register_shuffle_rule()


def _register_writer_rule():
    from ..io.writers import CpuWriteFilesExec, TpuWriteFilesExec
    EXEC_RULES[CpuWriteFilesExec] = ExecRule(
        "DataWritingCommand",
        lambda n: [],
        lambda n, ch, conf: TpuWriteFilesExec(
            ch[0], n.fmt, n.path, n.options, n.partition_by, n.mode))


_register_writer_rule()


def _ml_score_tag(meta: ExecMeta, conf: TpuConf):
    """ModelScore gating: the subsystem kill-switch keeps the operator on
    the CPU oracle path (the bit-identity twin, docs/ml-integration.md);
    feature types must be device-numeric."""
    from ..config import TPU_ML_ENABLED
    if not conf.get(TPU_ML_ENABLED):
        meta.will_not_work(
            "spark.rapids.tpu.ml.enabled is false: ModelScore stays on "
            "the CPU oracle path")
    for e in meta.node.exprs:
        if not e.data_type.is_numeric:
            meta.will_not_work(
                f"model feature {e.name!r} of type {e.data_type} is not "
                "numeric")


def _register_ml_rule():
    from ..exec.ml_score import CpuModelScoreExec, TpuModelScoreExec
    EXEC_RULES[CpuModelScoreExec] = ExecRule(
        "ModelScore",
        lambda n: list(n.exprs),
        lambda n, ch, conf: TpuModelScoreExec(
            ch[0], n._ml_registry, n.model_name, n.model_version,
            n.exprs, n.output_col, n.schema),
        tag=_ml_score_tag)


_register_ml_rule()


def _make_nlj(n: "P.CpuNestedLoopJoinExec", ch):
    from ..exec.joins import (TpuBroadcastExchangeExec,
                              TpuBroadcastNestedLoopJoinExec,
                              TpuCartesianProductExec)
    if n.join_type == "cross" and n.condition is None:
        return TpuCartesianProductExec(ch[0], ch[1], n.schema)
    return TpuBroadcastNestedLoopJoinExec(
        ch[0], TpuBroadcastExchangeExec(ch[1]), n.join_type, n.condition,
        n.schema)

#: Node types that legitimately stay on CPU (host-side sources; the scan
#: device-decode path is a later milestone, like the reference's host-read +
#: device-decode split). DeviceSourceExec is already device-resident and
#: needs no replacement rule.
HOST_SOURCE_NODES = ("CpuLocalScanExec", "CpuFileScanExec",
                     "DeviceSourceExec")


class FallbackOnTpuError(AssertionError):
    """Raised in test mode when an op unexpectedly stayed on CPU
    (spark.rapids.sql.test.enabled analog, RapidsConf.scala:478)."""


class TpuOverrides:
    """The rewrite pass. apply() tags, optionally explains, converts, and
    inserts transitions."""

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.last_explain: str = ""

    def wrap(self, node: P.PhysicalPlan) -> ExecMeta:
        children = [self.wrap(c) for c in node.children]
        rule = EXEC_RULES.get(type(node))
        return ExecMeta(node, rule, children)

    def apply(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        if not self.conf.sql_enabled:
            return plan
        meta = self.wrap(plan)
        meta.tag(self.conf)
        # Host-source nodes aren't failures; clear the no-rule reason.
        self._absolve_sources(meta)
        explain = self.conf.explain
        if explain in ("ALL", "NOT_ON_TPU"):
            self.last_explain = meta.explain(all_nodes=(explain == "ALL"))
            if self.last_explain:
                print(self.last_explain, end="")
        converted = finalize_plan(meta.convert(self.conf), self.conf)
        if self.conf.test_enabled:
            self._assert_on_tpu(converted)
        return converted

    def _absolve_sources(self, meta: ExecMeta):
        if meta.node.node_name() in HOST_SOURCE_NODES:
            meta.reasons = [r for r in meta.reasons
                            if not r.startswith("no TPU replacement")]
        for c in meta.children:
            self._absolve_sources(c)

    def _assert_on_tpu(self, plan: P.PhysicalPlan):
        allowed = set(self.conf.allowed_non_tpu) | set(HOST_SOURCE_NODES) | {
            "HostToDeviceExec", "DeviceToHostExec"}
        bad: List[str] = []

        def check(node):
            name = node.node_name()
            # Device-consuming host-output nodes (writers) are device execs:
            # the real invariant is "consumes device batches".
            consumes_device = getattr(node, "children_columnar", node.columnar)
            if not consumes_device and name not in allowed:
                bad.append(name)
            for c in node.children:
                check(c)
        check(plan)
        if bad:
            raise FallbackOnTpuError(
                f"ops fell back to CPU: {bad}; allowed={sorted(allowed)}")


def _device_scan_or_none(node: P.PhysicalPlan, conf: Optional[TpuConf]):
    """Swap an uploadable parquet/ORC host scan for the device decoder
    (io/parquet_device.py, io/orc_device.py) when every unit qualifies."""
    from ..config import (CSV_DEVICE_DECODE, ORC_DEVICE_DECODE,
                          PARQUET_DEVICE_DECODE)
    from ..io.files import CpuFileScanExec
    if conf is None or not isinstance(node, CpuFileScanExec):
        return None
    if node.pushed_filters or node.emit_file_meta:
        # input_file_name() queries synthesize metadata columns host-side;
        # the host scan + upload path handles them.
        return None
    if node.fmt == "csv" and conf.get(CSV_DEVICE_DECODE):
        from ..io import csv_device as CD
        try:
            CD_ok = CD.device_decodable(node.schema, node.options)
        except Exception:
            return None
        files = CD.scan_files(node.paths) if CD_ok else []
        if not files:
            return None
        # Hive-partitioned layouts synthesize the key=value directory
        # columns at read time; the per-file device parse (and its
        # per-file host fallback) sees only the file's own fields, so
        # partitioned directories keep the host dataset reader. Only
        # components BELOW the scanned roots count — an '=' in the user's
        # base path is not a partition.
        roots = [os.path.abspath(p) for p in node.paths]

        def below_root(f):
            af = os.path.abspath(f)
            for r in roots:
                if af.startswith(r + os.sep):
                    return os.path.relpath(os.path.dirname(af), r)
            return ""
        if any("=" in part for f in files
               for part in below_root(f).split(os.sep)):
            return None
        return CD.TpuCsvScanExec(files, node.schema, node._file_schema,
                                 node.options)
    if node.fmt == "orc" and conf.get(ORC_DEVICE_DECODE):
        from ..io import orc_device as OD
        files = OD.scan_files(node.paths)
        if not files:
            return None
        tails = {}
        for f in files:
            try:
                tail = OD.read_tail(f)
            except Exception:
                return None
            if not OD.device_decodable(f, node.schema, tail):
                return None
            tails[f] = tail
        return OD.TpuOrcScanExec(files, node.schema, node._file_schema, tails)
    if not conf.get(PARQUET_DEVICE_DECODE):
        return None
    if node.fmt != "parquet":
        return None
    from ..io import parquet_device as PD
    files = PD.scan_files(node.paths)
    if not files:
        return None
    import pyarrow.parquet as pq
    pf_cache = {}
    for f in files:
        try:
            with pq.ParquetFile(f) as pf:
                ok = PD.device_decodable(f, node.schema, pf=pf)
                # Keep parsed metadata only — no open descriptors on plans.
                pf_cache[f] = (pf.metadata, pf.schema)
        except Exception:
            return None
        if not ok:
            return None
    return PD.TpuParquetScanExec(files, node.schema, node._file_schema,
                                 pf_cache)


def finalize_plan(plan: P.PhysicalPlan, conf: TpuConf) -> P.PhysicalPlan:
    """Make a converted tree executable: insert host/device transitions and
    batch coalescing. The tail of ``TpuOverrides.apply`` — also used by the
    session's plan-lint warn-fallback, which must prepare its CPU plan the
    same way as every other plan the session emits."""
    from ..exec.coalesce import insert_coalesce
    plan = insert_transitions(plan, conf.batch_size_rows, conf)
    return insert_coalesce(plan, conf.batch_size_rows)


def insert_transitions(plan: P.PhysicalPlan,
                       goal_rows: int = 1 << 20,
                       conf: Optional[TpuConf] = None) -> P.PhysicalPlan:
    """Insert HostToDevice/DeviceToHost where columnar-ness flips, and make
    the root host-side (GpuTransitionOverrides analog)."""

    def fix(node: P.PhysicalPlan) -> P.PhysicalPlan:
        # Some nodes consume device batches but emit host output (writers:
        # device child, host stats row); children_columnar overrides the
        # child-side decision.
        wants_columnar = getattr(node, "children_columnar", node.columnar)
        new_children = []
        for c in fixed_children(node):
            if wants_columnar and not c.columnar:
                dev_scan = _device_scan_or_none(c, conf)
                c = dev_scan if dev_scan is not None \
                    else E.HostToDeviceExec(c, goal_rows)
            elif not wants_columnar and c.columnar:
                c = E.DeviceToHostExec(c)
            new_children.append(c)
        if list(new_children) != list(node.children):
            node = node.with_children(new_children)
        return node

    def fixed_children(node):
        return [fix(c) for c in node.children]

    root = fix(plan)
    if root.columnar:
        root = E.DeviceToHostExec(root)
    return root
