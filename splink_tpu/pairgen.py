"""Device-side candidate-pair generation: the virtual pair index.

At the 10M-row configs HOST pair materialisation is the cost to avoid:
config 4 has 3.3B candidate pairs, and every materialised pair costs 8
bytes of host->device index traffic plus (spilled) 8 bytes of disk write
and re-read. (The rates this was first argued from — host joins 8.2M
pairs/s on one CPU core, the chip 28M+/s in a builders' session of round
4, older than the code — were never reproduced; what the virtual index
delivers end to end is PERF.md's `c4_dedupe_virtual`.)
This module removes the pairs from the host entirely for
equality-rule blocking: pairs are DECODED ON DEVICE from per-rule group
structure, the sequential-rule dedup becomes an on-device mask, and the
gamma/pattern program consumes them in the same kernel — per batch the
host ships only a few KB of unit metadata. The reference leaned on Spark
to materialise the same join (/root/reference/splink/blocking.py:145-158);
a TPU has no shuffle engine, but it doesn't need one: a blocked self-join
is group arithmetic, and arithmetic is what the chip does.

Decomposition. Each rule's non-null key groups (rows sorted by uid rank
then grouped by key code — exactly `_self_join`'s layout, so orientation
is free) split into UNITS of bounded extent:

  * triangle  — all unordered pairs within one chunk of <= CHUNK rows;
  * rectangle — all cross pairs between two chunks of <= CHUNK rows
    (two chunks of one group, or a left x right chunk pair in link_only).

Bounded extent is what makes the device decode exact WITHOUT int64/f64
(TPU has neither by default): within a unit the pair offset t fits int32,
the triangle discriminant (2s-1)^2 - 8t stays below 2^24 so the f32 sqrt
is exact (one +-1 integer correction), and a rectangle decode is an int32
div/mod. Positions across units are int64 ONLY on the host: each device
batch receives the batch-relative int32 slice of the unit cumulative-pair
table plus a scalar unit offset.

Masking replaces dropping (XLA wants static shapes): a pair whose uid
keys collide (duplicate-uid inputs) or for which an EARLIER rule's
predicate holds (the reference's ``AND NOT ifnull(prev, false)``,
/root/reference/splink/blocking.py:59-68) gets the sentinel pattern id
``n_patterns`` and falls out of the histogram's overflow bucket; the
output stream filters on the sentinel. The kernel hands the row pairs it
decoded back beside the ids, so the stream never decodes a position again.

An EMPTY rule list (every pair of rows, the reference's behaviour with no
rule) is one keyless group of all rows in the same plan: past
``max_resident_pairs`` (100,000 rows are 5.0e9 pairs) the all-pairs job runs
on the virtual index like any other.

Supported: all three link types on a single device — link_and_dedupe
self-joins the concatenated table ordered by (source, uid), link_only
tiles left x right group rectangles. Residual (non-equality) predicates
compile to DEVICE masks mirroring residual_eval's SQL three-valued
semantics: any column (encoded string, numeric, raw passthrough)
compares via scaled int32 lexicographic ranks (null = -2; literals bind
to 2*pos or the odd insertion rank; cross-column compares re-rank over
the union vocabulary), numeric contexts use NaN-null float arrays with
the host's pd.to_numeric coercion applied once at plan build. Predicates
the device can't honour (unsortable mixed-type columns, literal/column
type mismatches) reject the plan and fall back to host blocking, as does a
rule with no equality conjunct beside a residual. Note:
on TPU numeric residual thresholds evaluate in f32 (the chip has no
f64), so a pair exactly on a threshold may land differently than the
f64 host path — the CPU tier (x64) is bit-identical.
"""

from __future__ import annotations

import ast
import functools
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from .blocking import (
    _key_codes,
    _sort_groups,
    _split_join_keys,
    _uid_ranks,
    parse_blocking_rule,
)
from .data import EncodedTable
from .gammas import int32_histogram, pattern_ids_fit_uint16
from .utils import profiling
from .utils.kernel_registry import mesh_key

# Unit extent bound. 2048 keeps the triangle discriminant (2s-1)^2 < 2^24
# (f32-exact) and a rectangle's pair count at 2048^2 ~ 4.2M (int32-safe);
# tests shrink it to force multi-chunk group splitting on tiny data.
CHUNK = 2048

# A single group may contribute at most this many units (the unit-order
# sort key packs (group, unit-seq) as group*2^20 + seq). k chunks give
# k(k+1)/2 units, so this caps a group at ~1448 chunks ~ 2.9M rows SHARING
# ONE KEY — effectively a constant blocking column, where a plan this
# shape is the wrong tool anyway; such inputs fall back to host blocking.
MAX_UNITS_PER_GROUP = (1 << 20) - 1

# Concurrent downloads in a virtual pass that keeps ids (a batch's pattern
# ids and row pairs): how many batches may be in flight on the D2H thread
# pool before the driver blocks.
# Bounded so pid buffers are not pinned on device without limit. The value 3
# is not tuned: the cell ``c5_dedupe_stream`` (PERF.md §5) is where both
# passes run several batches deep and reads what it costs (``d2h_wait``).
_D2H_DEPTH = 3


@dataclass
class RulePlan:
    """One rule's device-decodable join structure."""

    order: np.ndarray  # (n_valid,) int32 rows sorted by (key code, uid rank)
    ua: np.ndarray  # (U,) int32 unit a-side start into `order`
    la: np.ndarray  # (U,) int32 a-side extent (<= CHUNK)
    ub: np.ndarray  # (U,) int32 b-side start (== ua for triangles)
    lb: np.ndarray  # (U,) int32 b-side extent
    pc: np.ndarray  # (U+1,) int64 cumulative pair counts over units
    residual: str | None = None  # translated residual predicate source
    residual_fn: object = None  # compiled device closure (see _ResCompiler)
    # (batch size, mesh key) -> (kernel, the abstract arguments
    # — shape, dtype, sharding — of its first call): the pattern kernels
    # this rule has RUN, for compiled_kernel_texts. The kernels themselves
    # are the process's (utils/kernel_registry via GammaProgram._kernel):
    # jax.jit caches on function identity, so every pass and every linker
    # on the same model has to call the SAME jitted function.
    kernels_run: dict = field(default_factory=dict)

    @property
    def total(self) -> int:
        return int(self.pc[-1]) if len(self.pc) else 0


@dataclass
class VirtualPlan:
    rules: list[RulePlan]
    codes: np.ndarray  # (R, n) int32 per-rule key codes (device dedup mask)
    uid_codes: np.ndarray | None  # (n,) int32 when duplicate uids exist
    n_candidates: int  # sum of rule totals (mask not yet applied)
    res_ops: list[np.ndarray] = field(default_factory=list)  # residual operand arrays
    table: EncodedTable | None = None  # for host-side residual oracle
    chunk: int = CHUNK  # unit extent the plan was built with (int32 margin)

    def rule_offsets(self) -> np.ndarray:
        """(R+1,) int64 global position offset of each rule's segment."""
        return np.concatenate(
            [[0], np.cumsum([rp.total for rp in self.rules])]
        ).astype(np.int64)


# --------------------------------------------------------------------------
# Residual predicates -> device closures
# --------------------------------------------------------------------------


class _ResUnsupported(Exception):
    """The residual needs something the device can't honour (object
    columns, cross-vocabulary string compares, string-to-number coercion);
    the plan falls back to host blocking."""


def _cmp_apply(opname, x, y):
    """One comparison. A module function and not a method: the closures the
    compiler returns must not hold the compiler, whose table they would
    keep alive for as long as a kernel that traced them is registered."""
    return {
        "eq": lambda: x == y,
        "ne": lambda: x != y,
        "lt": lambda: x < y,
        "le": lambda: x <= y,
        "gt": lambda: x > y,
        "ge": lambda: x >= y,
    }[opname]()


class _ResCompiler:
    """Compile a translated residual predicate (the same python-expression
    surface residual_eval interprets) into a jax-traceable closure
    fn(i, j, ops) -> (val, unk) with SQL three-valued semantics.

    Per-row operand arrays register once per column and upload once per
    run: string columns as scaled int32 ranks (2*rank; null -2 — literals
    bind to 2*pos, or the odd 2*pos-1 insertion rank so an absent literal
    orders correctly and equals nothing), numerics as NaN-null floats.
    """

    _CMPS = {
        ast.Eq: "eq", ast.NotEq: "ne", ast.Lt: "lt", ast.LtE: "le",
        ast.Gt: "gt", ast.GtE: "ge",
    }
    _ARITH = {
        ast.Add: "add", ast.Sub: "sub", ast.Mult: "mul", ast.Div: "div",
        ast.Mod: "mod", ast.Pow: "pow",
    }

    def __init__(self, table: EncodedTable, ops: list[np.ndarray],
                 op_index: dict, aux: dict):
        self.table = table
        self.ops = ops  # shared across rules; uploaded once
        self.op_index = op_index  # key -> position in ops
        self.aux = aux  # vocab arrays for literal binding (host-only)
        # everything the compile took from the TABLE, in order — operand
        # slots, literal ranks, column kinds: with the source it determines
        # the closure (compile_residual_device signs the closure with it)
        self.trail: list = []

    def _register(self, key, build) -> int:
        if key not in self.op_index:
            self.op_index[key] = len(self.ops)
            self.ops.append(build())
        self.trail.append(("slot", key, self.op_index[key]))
        return self.op_index[key]

    def _col_values_null(self, col):
        if isinstance(col, tuple) and col[0] == "expr":
            # a derived pseudo-column: a single-side SQL function
            # subexpression precomputed host-side (see _derived_value)
            from .derived_keys import key_values_object

            return key_values_object(self.table, col[1])
        vals = np.asarray(self.table.column_values(col), dtype=object)
        null = self.table.is_null(col)
        return vals, null

    def _vocab(self, col: str) -> np.ndarray:
        """Same-column / literal-binding vocabulary: ENCODED string columns
        use the table's string_ranks vocabulary (which str()-coerces,
        exactly what the host's StrOperand compares through); raw
        passthrough columns sort their raw object values (the host's
        RawOperand compares those elementwise)."""
        key = ("vocab", col)
        if key not in self.aux:
            if col in self.table.strings:
                self.aux[key] = self.table.string_ranks(col)[1]
            else:
                vals, null = self._col_values_null(col)
                try:
                    self.aux[key] = np.unique(vals[~null])
                except TypeError as e:  # mixed incomparable types
                    raise _ResUnsupported(f"unsortable column {col!r}") from e
        return self.aux[key]

    def _str_ranks_scaled(self, col: str) -> int:
        """Scaled rank array (2*rank; null -2), order-isomorphic to the
        host's same-column comparison for this column kind."""
        self._vocab(col)  # validate sortability before registering

        def build():
            if col in self.table.strings:
                ranks, _ = self.table.string_ranks(col)
                return np.where(
                    np.isnan(ranks), -2, 2 * np.nan_to_num(ranks)
                ).astype(np.int32)
            vocab = self._vocab(col)
            vals, null = self._col_values_null(col)
            out = np.full(len(vals), -2, np.int64)
            nn = ~null
            out[nn] = 2 * np.searchsorted(vocab, vals[nn])
            return out.astype(np.int32)

        return self._register(("str", col), build)

    def _joint_ranks_scaled(self, cola: str, colb: str) -> tuple[int, int]:
        """Two scaled-rank arrays over the UNION of raw-value
        vocabularies — the host compares cross-column operands by their
        raw object VALUES (StrOperand.values), so both sides rank over raw
        values here regardless of encoding. Keys are canonicalised so
        (a, b) and (b, a) share one array pair."""

        def raw_vocab(col):
            vals, null = self._col_values_null(col)
            try:
                return np.unique(vals[~null])
            except TypeError as e:
                raise _ResUnsupported(f"unsortable column {col!r}") from e

        # key=repr: plain column names (str) and derived pseudo-columns
        # (("expr", canon) tuples) are not mutually orderable
        c1, c2 = sorted((cola, colb), key=repr)
        union_key = ("joint_vocab", c1, c2)
        if union_key not in self.aux:
            try:
                self.aux[union_key] = np.unique(
                    np.concatenate([raw_vocab(c1), raw_vocab(c2)])
                )
            except TypeError as e:
                raise _ResUnsupported(
                    f"unsortable column pair {cola!r}/{colb!r}"
                ) from e
        union = self.aux[union_key]

        def build_for(col):
            def build():
                vals, null = self._col_values_null(col)
                out = np.full(len(vals), -2, np.int64)
                nn = ~null
                out[nn] = 2 * np.searchsorted(union, vals[nn])
                return out.astype(np.int32)

            return build

        ia = self._register(("joint", c1, c2, c1), build_for(c1))
        ib = self._register(("joint", c1, c2, c2), build_for(c2))
        return (ia, ib) if cola == c1 else (ib, ia)

    def _numeric_vals(self, col: str) -> int:
        def build():
            nc = self.table.numerics[col]
            vals = nc.values_f64.copy()
            vals[nc.null_mask] = np.nan
            return vals

        return self._register(("num", col), build)

    def _coerced_vals(self, col: str) -> int:
        """SQL numeric-context coercion of a string/raw column (the host's
        pd.to_numeric path) — computed host-side once, NaN for null or
        unparseable."""

        def build():
            import pandas as pd

            vals, null = self._col_values_null(col)
            out = pd.to_numeric(pd.Series(vals), errors="coerce").to_numpy(
                dtype=np.float64, copy=True
            )
            out[null] = np.nan
            return out

        return self._register(("coerce", col), build)

    def _literal_rank(self, col: str, lit) -> int:
        vocab = self._vocab(col)
        if len(vocab) and not isinstance(lit, type(vocab[0])):
            # comparing e.g. a number literal against a string column would
            # TypeError on the host too — reject rather than guess
            raise _ResUnsupported(
                f"literal {lit!r} vs column {col!r} type mismatch"
            )
        pos = int(np.searchsorted(vocab, lit))
        # odd: orders correctly, equals nothing
        rank = 2 * pos if pos < len(vocab) and vocab[pos] == lit else 2 * pos - 1
        self.trail.append(("literal", col, lit, rank))
        return rank

    # -- value level: returns ("str", col, op_idx, side) |
    #    ("num", fn(i,j,ops)->float array) | ("lit_s", s) | ("lit_n", x)
    def value(self, node):
        if isinstance(node, ast.Subscript):
            if not (
                isinstance(node.value, ast.Name)
                and node.value.id in ("l", "r")
                and isinstance(node.slice, ast.Constant)
                and isinstance(node.slice.value, str)
            ):
                raise _ResUnsupported("subscript shape")
            col = node.slice.value
            side = node.value.id
            self.trail.append(("numeric", col, col in self.table.numerics))
            if col in self.table.numerics:
                idx = self._numeric_vals(col)
                return ("num", self._gather_num(idx, side))
            if col in self.table.strings or col in self.table.raw:
                # encoded strings and raw passthrough columns both compare
                # via lexicographic ranks; the rank array registers LAZILY
                # at the use site (a column used only in cross-column
                # compares needs the joint arrays, not its own)
                return ("str", col, None, side)
            raise _ResUnsupported(f"unknown column {col!r}")
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str):
                return ("lit_s", node.value)
            if isinstance(node.value, (int, float)) and not isinstance(
                node.value, bool
            ):
                return ("lit_n", float(node.value))
            raise _ResUnsupported(f"literal {node.value!r}")
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            inner = self.value(node.operand)
            if inner[0] == "lit_n":
                return ("lit_n", -inner[1])
            if inner[0] == "num":
                f = inner[1]
                return ("num", lambda i, j, ops: -f(i, j, ops))
            raise _ResUnsupported("unary minus on non-numeric")
        if isinstance(node, ast.BinOp) and type(node.op) in self._ARITH:
            a = self._as_num(self.value(node.left))
            b = self._as_num(self.value(node.right))
            opname = self._ARITH[type(node.op)]

            def arith(i, j, ops, a=a, b=b, opname=opname):
                import jax.numpy as jnp

                x, y = a(i, j, ops), b(i, j, ops)
                return {
                    "add": lambda: x + y,
                    "sub": lambda: x - y,
                    "mul": lambda: x * y,
                    "div": lambda: x / y,
                    # host parity: SQL % takes the dividend's sign
                    "mod": lambda: jnp.fmod(x, y),
                    "pow": lambda: x**y,
                }[opname]()

            return ("num", arith)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            # `@` = compat_sql's translation of SQL's `||` concat operator
            return self._derived_value(node)
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) and node.func.id == "abs":
                (arg,) = node.args
                f = self._as_num(self.value(arg))

                def absf(i, j, ops, f=f):
                    import jax.numpy as jnp

                    return jnp.abs(f(i, j, ops))

                return ("num", absf)
            return self._derived_value(node)
        raise _ResUnsupported(f"value node {type(node).__name__}")

    def _derived_value(self, node):
        """Single-side SQL scalar function subexpressions (substr, lower,
        concat, coalesce, length, ..., and ``@`` = SQL ``||``) precompute
        host-side into a per-row derived operand via derived_keys — the
        SAME implementation of the function semantics the host residual
        interpreter and the blocking join keys use — then compare on
        device by rank like any column. Functions mixing both sides in one
        call (concat(l.a, r.b)) have no per-row precompute; those reject
        the plan (host fallback)."""
        from .derived_keys import (
            DerivedKeyError,
            canonical,
            evaluate_key,
            expr_sides,
            pyast_to_keynode,
            strip_side,
        )

        try:
            knode = pyast_to_keynode(node)
        except DerivedKeyError as e:
            raise _ResUnsupported(str(e)) from None
        sides = expr_sides(knode)
        if len(sides) != 1:
            raise _ResUnsupported("cross-side function subexpression")
        (side,) = sides
        canon = canonical(strip_side(knode))
        try:
            kind, vals, null = evaluate_key(self.table, canon)
        except DerivedKeyError as e:
            raise _ResUnsupported(str(e)) from None
        self.trail.append(("derived", canon, kind))
        if kind == "num":

            def build(vals=vals, null=null):
                out = vals.copy()
                out[null] = np.nan
                return out

            idx = self._register(("dnum", canon), build)
            return ("num", self._gather_num(idx, side))
        return ("str", ("expr", canon), None, side)

    @staticmethod
    def _gather_num(idx: int, side: str):
        def g(i, j, ops):
            rows = i if side == "l" else j
            return ops[idx][rows]

        return g

    def _as_num(self, v):
        """Numeric closure from a value. String/raw columns coerce through
        the host's pd.to_numeric ONCE at plan build (the array uploads like
        any other operand), matching SQL's implicit CAST semantics."""
        # marker for build_virtual_plan's f32-divergence warning: numeric
        # arithmetic in a device residual evaluates in f32 on TPU
        self.aux["numeric_used"] = True
        if v[0] == "num":
            return v[1]
        if v[0] == "lit_n":
            x = v[1]

            def const(i, j, ops, x=x):
                import jax
                import jax.numpy as jnp

                # session float dtype: f64 under x64 keeps literal
                # thresholds bit-identical to the host path
                dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
                return jnp.full(i.shape, x, dt)

            return const
        if v[0] == "str":
            return self._gather_num(self._coerced_vals(v[1]), v[3])
        raise _ResUnsupported("non-numeric operand in numeric context")

    # -- comparisons -> (val, unk) closures
    def compare_pair(self, opname, lv, rv):
        if lv[0] == "str" and rv[0] == "str":
            if lv[1] == rv[1]:
                li = ri = self._str_ranks_scaled(lv[1])
            else:
                # different vocabularies: re-rank both over the union
                li, ri = self._joint_ranks_scaled(lv[1], rv[1])
            ls, rs = lv[3], rv[3]

            def f(i, j, ops, li=li, ls=ls, ri=ri, rs=rs, opname=opname):
                a = ops[li][i if ls == "l" else j]
                b = ops[ri][i if rs == "l" else j]
                unk = (a < 0) | (b < 0)
                return _cmp_apply(opname, a, b) & ~unk, unk

            return f
        if lv[0] == "str" and rv[0] == "lit_s":
            k = self._literal_rank(lv[1], rv[1])
            li, ls = self._str_ranks_scaled(lv[1]), lv[3]

            def f(i, j, ops, li=li, ls=ls, k=k, opname=opname):
                a = ops[li][i if ls == "l" else j]
                unk = a < 0
                return _cmp_apply(opname, a, k) & ~unk, unk

            return f
        if rv[0] == "str" and lv[0] == "lit_s":
            k = self._literal_rank(rv[1], lv[1])
            ri, rs = self._str_ranks_scaled(rv[1]), rv[3]

            def f(i, j, ops, ri=ri, rs=rs, k=k, opname=opname):
                b = ops[ri][i if rs == "l" else j]
                unk = b < 0
                return _cmp_apply(opname, k, b) & ~unk, unk

            return f
        # numeric comparison — a BARE string column here is a type
        # mismatch on the host (evaluate_residual raises; coercion only
        # happens inside arithmetic/abs contexts), so reject for parity
        if lv[0] == "str" or rv[0] == "str":
            raise _ResUnsupported(
                "string column in a numeric comparison (host type mismatch)"
            )
        a = self._as_num(lv)
        b = self._as_num(rv)

        def f(i, j, ops, a=a, b=b, opname=opname):
            import jax.numpy as jnp

            x, y = a(i, j, ops), b(i, j, ops)
            unk = jnp.isnan(x) | jnp.isnan(y)
            return _cmp_apply(opname, x, y) & ~unk, unk

        return f

    # -- boolean level (Kleene from residual_eval works on jax arrays too:
    # its operators are pure &, |, ~ algebra — ONE implementation of the
    # null logic shared between host and device)
    def boolean(self, node):
        import jax.numpy as jnp

        from .residual_eval import Kleene

        if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.BitAnd, ast.BitOr)
        ):
            a = self.boolean(node.left)
            b = self.boolean(node.right)
            is_and = isinstance(node.op, ast.BitAnd)

            def f(i, j, ops, a=a, b=b, is_and=is_and):
                ka = Kleene(*a(i, j, ops))
                kb = Kleene(*b(i, j, ops))
                out = (ka & kb) if is_and else (ka | kb)
                return out.val, out.unk

            return f
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Invert):
            a = self.boolean(node.operand)

            def f(i, j, ops, a=a):
                out = ~Kleene(*a(i, j, ops))
                return out.val, out.unk

            return f
        if isinstance(node, ast.Compare):
            operands = [node.left, *node.comparators]
            parts = []
            for op, ln, rn in zip(node.ops, operands, operands[1:]):
                if type(op) not in self._CMPS:
                    raise _ResUnsupported("comparison operator")
                parts.append(
                    self.compare_pair(
                        self._CMPS[type(op)], self.value(ln), self.value(rn)
                    )
                )

            def f(i, j, ops, parts=parts):
                out = Kleene(*parts[0](i, j, ops))
                for p in parts[1:]:
                    out = out & Kleene(*p(i, j, ops))
                return out.val, out.unk

            return f
        if isinstance(node, ast.Call):
            if not (
                isinstance(node.func, ast.Name) and node.func.id == "_isna"
            ):
                raise _ResUnsupported("boolean call")
            (arg,) = node.args
            v = self.value(arg)
            if v[0] == "str":
                oi, side = self._str_ranks_scaled(v[1]), v[3]

                def f(i, j, ops, oi=oi, side=side):
                    a = ops[oi][i if side == "l" else j]
                    return a < 0, jnp.zeros(a.shape, bool)

                return f
            if v[0] == "num":
                g = v[1]

                def f(i, j, ops, g=g):
                    import jax.numpy as jnp

                    x = g(i, j, ops)
                    return jnp.isnan(x), jnp.zeros(x.shape, bool)

                return f
            raise _ResUnsupported("_isna of a literal")
        if isinstance(node, ast.Constant) and isinstance(node.value, bool):
            b = bool(node.value)

            def f(i, j, ops, b=b):
                import jax.numpy as jnp

                return jnp.full(i.shape, b), jnp.zeros(i.shape, bool)

            return f
        raise _ResUnsupported(f"boolean node {type(node).__name__}")


def compile_residual_device(table, residual_src: str,
                            ops: list[np.ndarray], op_index: dict,
                            aux: dict):
    """-> fn(i, j, ops) -> (val, unk), or None when the predicate needs
    host-only machinery (the caller then rejects the whole plan).

    ``fn.signature`` is the closure BY VALUE: the source and, in order,
    everything the compile took from the table (operand slots, literal
    ranks, column kinds). Two closures with equal signatures trace to the
    same program, so the kernels that compose them can be shared across
    linkers (make_virtual_pattern_fn); the closure holds no table."""
    try:
        tree = ast.parse(residual_src, mode="eval")
    except SyntaxError:
        return None
    compiler = _ResCompiler(table, ops, op_index, aux)
    try:
        fn = compiler.boolean(tree.body)
    except _ResUnsupported:
        return None
    fn.signature = (residual_src, tuple(compiler.trail))
    return fn


def residual_signatures(residuals) -> tuple | None:
    """The signatures of compiled residual closures (None entries: no
    residual), as part of a kernel's registry key — or None when one of them
    carries no signature, so a kernel that traces it cannot be keyed."""
    if all(r is None or hasattr(r, "signature") for r in residuals):
        return tuple(None if r is None else r.signature for r in residuals)
    return None


def _split_extents(n: int, chunk: int) -> np.ndarray:
    """[chunk, chunk, ..., remainder] covering n."""
    k = -(-n // chunk)
    out = np.full(k, chunk, np.int64)
    if n % chunk:
        out[-1] = n % chunk
    return out


def _units_for_self_join(starts, sizes, chunk):
    """Triangle + rectangle units for within-group pairs, group by group.
    Returns None when a group would exceed MAX_UNITS_PER_GROUP."""
    if len(sizes):
        k_max = -(-int(sizes.max()) // chunk)
        if k_max * (k_max + 1) // 2 > MAX_UNITS_PER_GROUP:
            return None
    ua, la, ub, lb = [], [], [], []
    big = sizes > chunk
    # fast path: single-chunk groups (one triangle each)
    small = (~big) & (sizes >= 2)
    ua.append(starts[small])
    la.append(sizes[small])
    ub.append(starts[small])
    lb.append(sizes[small])
    key = [np.flatnonzero(small).astype(np.int64) * (1 << 20)]
    for gi in np.flatnonzero(big):
        s0, s = int(starts[gi]), int(sizes[gi])
        exts = _split_extents(s, chunk)
        offs = np.concatenate([[0], np.cumsum(exts)])[:-1] + s0
        k = len(exts)
        gua, gla, gub, glb = [], [], [], []
        for c in range(k):
            gua.append(offs[c])
            gla.append(exts[c])
            gub.append(offs[c])
            glb.append(exts[c])
            for c2 in range(c + 1, k):
                gua.append(offs[c])
                gla.append(exts[c])
                gub.append(offs[c2])
                glb.append(exts[c2])
        ua.append(np.asarray(gua, np.int64))
        la.append(np.asarray(gla, np.int64))
        ub.append(np.asarray(gub, np.int64))
        lb.append(np.asarray(glb, np.int64))
        key.append(
            gi * (1 << 20) + 1 + np.arange(len(gua), dtype=np.int64)
        )
    ua = np.concatenate(ua)
    la = np.concatenate(la)
    ub = np.concatenate(ub)
    lb = np.concatenate(lb)
    key = np.concatenate(key)
    # deterministic unit order: by (group, within-group unit sequence)
    o = np.argsort(key, kind="stable")
    return ua[o], la[o], ub[o], lb[o]


def _units_for_cross_join(ls, lz, rs, rz, chunk):
    """Rectangle units for left x right group pairs (link types).
    Returns None when a group would exceed MAX_UNITS_PER_GROUP."""
    if len(lz):
        per_group = (-(-lz // chunk)) * (-(-rz // chunk))
        if int(per_group.max()) > MAX_UNITS_PER_GROUP:
            return None
    ua, la, ub, lb = [], [], [], []
    both_small = (lz <= chunk) & (rz <= chunk)
    ua.append(ls[both_small])
    la.append(lz[both_small])
    ub.append(rs[both_small])
    lb.append(rz[both_small])
    key = [np.flatnonzero(both_small).astype(np.int64) * (1 << 20)]
    for gi in np.flatnonzero(~both_small):
        lex = _split_extents(int(lz[gi]), chunk)
        loff = np.concatenate([[0], np.cumsum(lex)])[:-1] + int(ls[gi])
        rex = _split_extents(int(rz[gi]), chunk)
        roff = np.concatenate([[0], np.cumsum(rex)])[:-1] + int(rs[gi])
        gua, gla, gub, glb = [], [], [], []
        for a in range(len(lex)):
            for b in range(len(rex)):
                gua.append(loff[a])
                gla.append(lex[a])
                gub.append(roff[b])
                glb.append(rex[b])
        ua.append(np.asarray(gua, np.int64))
        la.append(np.asarray(gla, np.int64))
        ub.append(np.asarray(gub, np.int64))
        lb.append(np.asarray(glb, np.int64))
        key.append(gi * (1 << 20) + 1 + np.arange(len(gua), dtype=np.int64))
    ua = np.concatenate(ua)
    la = np.concatenate(la)
    ub = np.concatenate(ub)
    lb = np.concatenate(lb)
    key = np.concatenate(key)
    o = np.argsort(key, kind="stable")
    return ua[o], la[o], ub[o], lb[o]


def _pair_counts(ua, la, ub, lb) -> np.ndarray:
    tri = ua == ub
    cnt = np.where(tri, la * (la - 1) // 2, la * lb).astype(np.int64)
    return np.concatenate([[0], np.cumsum(cnt)])


def _uid_mask_codes(table: EncodedTable, link_type: str) -> np.ndarray | None:
    """Dense int32 ordering-key codes for the device duplicate-uid mask, or
    None when the ordering keys are unique (the common case — then the
    strict rank ordering alone reproduces the reference's l.key < r.key).
    link_and_dedupe keys are (source, uid), the reference's `_source_table`
    tie-break (/root/reference/splink/blocking.py:139)."""
    _, keys_unique = _uid_ranks(table, link_type)
    if keys_unique:
        return None
    uid = np.asarray(table.unique_id)
    _, uid_codes = np.unique(uid, return_inverse=True)
    uid_codes = uid_codes.astype(np.int64)
    if link_type == "link_and_dedupe":
        uid_codes = uid_codes * 2 + np.asarray(table.source_table, np.int64)
        _, uid_codes = np.unique(uid_codes, return_inverse=True)
    return uid_codes.astype(np.int32)


def _unit_batch_meta(pc: np.ndarray, total: int, rule_bs: int,
                     kpad_min: int = 0):
    """One metadata row [u0, valid, pc_rel...] per batch of ``rule_bs``
    positions, padded to ONE power-of-two kpad for the whole rule (one
    kernel specialisation per rule). pc_rel entries past the last unit
    (and padding) are int32 max and fall out of the unit lookup; the int32
    clip cannot corrupt in-batch positions because the driver already
    clamped the batch size below 2^31 - chunk^2.

    ``kpad_min`` floors the pad width: the SHARDED emission driver splits a
    rule's units across shards whose natural kpads can differ, and the
    meta row's length is part of the kernel's compiled shape — flooring
    every shard at the rule-wide maximum keeps all of a rule's segments on
    ONE specialisation (the zero-steady-state-recompiles contract)."""
    starts = list(range(0, total, rule_bs))
    u0s, u1s = [], []
    for p0 in starts:
        p1 = min(p0 + rule_bs, total)
        u0s.append(int(np.searchsorted(pc, p0, side="right")) - 1)
        u1s.append(int(np.searchsorted(pc, p1 - 1, side="right")) - 1)
    kmax = max(u1 - u0 + 2 for u0, u1 in zip(u0s, u1s))
    kpad = 1 << int(max(kmax, 2) - 1).bit_length()
    kpad = max(kpad, int(kpad_min))
    imax = np.iinfo(np.int32).max
    out = []
    for b, p0 in enumerate(starts):
        u0, u1 = u0s[b], u1s[b]
        p1 = min(p0 + rule_bs, total)
        pc_rel = (pc[u0 : u1 + 2] - p0).astype(np.int64)
        meta = np.full(kpad + 2, imax, np.int32)
        meta[0] = u0
        meta[1] = p1 - p0
        meta[2 : u1 - u0 + 4] = np.clip(pc_rel, -(1 << 31) + 1, imax)
        out.append((p0, p1, meta))
    return out


def unit_decode(pos, order, ua, la, ub, lb, meta, *, mesh_ladder: bool):
    """Shared traced decode: batch-relative int32 positions -> (i, j, valid)
    row-index pairs, via the unit tables. The ONE implementation of the
    triangle/rectangle position decode, composed by the virtual pattern
    kernel here and the device blocking emission kernel
    (splink_tpu/blocking_device.py) — f32 math is exact because unit
    extents are bounded by CHUNK (module docstring)."""
    import jax.numpy as jnp

    u0 = meta[0]
    valid = meta[1]
    pc_slice = meta[2:]
    kpad = pc_slice.shape[0]
    bs = pos.shape[0]
    if not mesh_ladder:
        # positions are consecutive within the batch, so the unit
        # index is a monotone step function of pos: scatter +1 at
        # every unit start position and prefix-sum. One small
        # scatter-add (kpad updates) + one cumsum replaces a
        # log2(kpad)-step per-position binary search — the search's
        # ~11 gathers per position were the bulk of the decode cost
        # on chip (178ms/batch vs 43ms for the whole gamma+score).
        # pc_slice[1:] are the batch-relative starts of units
        # u0+1...; entries past the last unit (and padding) are int32
        # max and fall into the dropped overflow slot.
        starts = pc_slice[1:]
        idx = jnp.clip(starts, 0, bs)
        marks = jnp.zeros(bs + 1, jnp.int32).at[idx].add(
            jnp.where(starts < bs, 1, 0), mode="drop"
        )[:bs]
        ui = jnp.cumsum(marks, dtype=jnp.int32)
    else:
        # under a mesh, pos arrives SHARDED along the batch axis; a
        # cumsum there would need cross-device prefix comms, so keep
        # the branchless bit ladder: largest ui with
        # pc_slice[ui] <= pos (pc_slice is replicated, power-of-two
        # padded with int32 max, and pc_slice[0] <= 0 <= pos). NOT
        # jnp.searchsorted: its scan lowering wraps a vmapped while
        # loop XLA refuses to fuse through.
        ui = jnp.zeros_like(pos)
        half = kpad >> 1
        while half:
            cand = ui + half
            ui = jnp.where(pc_slice[cand] <= pos, cand, ui)
            half >>= 1
    t = pos - pc_slice[ui]
    u = u0 + ui
    # four separate 1-word gathers beat a packed (n_units, 4) row
    # gather here: the 4-wide minor dim pads to the 128 lane width on
    # TPU and wastes 32x the bandwidth (measured 2.19s vs 1.55s for
    # the 16M-position pass)
    A = ua[u]
    LA = la[u]
    Bs = ub[u]
    LB = lb[u]
    tri = A == Bs
    # triangle decode: f32 sqrt is exact for LA <= CHUNK (disc < 2^24),
    # then a +-1 integer correction absorbs the floor rounding
    lf = LA.astype(jnp.float32)
    tf = t.astype(jnp.float32)
    disc = (2.0 * lf - 1.0) ** 2 - 8.0 * tf
    a_t = jnp.floor(
        ((2.0 * lf - 1.0) - jnp.sqrt(jnp.maximum(disc, 0.0))) / 2.0
    ).astype(jnp.int32)

    def off(a):
        return a * LA - (a * (a + 1)) // 2

    a_t = jnp.where(off(a_t + 1) <= t, a_t + 1, a_t)
    a_t = jnp.where(off(a_t) > t, a_t - 1, a_t)
    b_t = t - off(a_t) + a_t + 1
    lb_safe = jnp.maximum(LB, 1)
    # rectangle decode without integer division (no VPU int-div; XLA
    # expands // by a non-constant into a long scalar sequence): f32
    # reciprocal multiply is within 1 of exact for t < 2^23 (unit
    # pair counts are < CHUNK^2 = 2^22), then a +-1 correction lands
    # it
    q = jnp.floor(
        t.astype(jnp.float32) * (1.0 / lb_safe.astype(jnp.float32))
    ).astype(jnp.int32)
    q = jnp.where((q + 1) * lb_safe <= t, q + 1, q)
    q = jnp.where(q * lb_safe > t, q - 1, q)
    a_r = q
    b_r = t - a_r * lb_safe
    a = jnp.where(tri, a_t, a_r)
    b = jnp.where(tri, b_t, b_r)
    i = order[A + a]
    j = order[Bs + b]
    return i, j, valid


def build_virtual_plan(
    settings: dict, table: EncodedTable, n_left: int | None = None,
    chunk: int | None = None,
) -> VirtualPlan | None:
    """Build the device-decodable plan, or None when unsupported (a rule
    with no equality conjunction, a residual predicate the device compiler
    can't honour, or a group past MAX_UNITS_PER_GROUP).

    An EMPTY rule list (the reference compares every pair then) is one
    KEYLESS rule: one group holding every row under a constant key code,
    tiled into units as any hot key is (10,000 rows = 5 chunks = 15 units);
    blocking_device.build_device_plan builds the same group for the
    materialised regime. Its ``keyless_pairs`` span (the one
    blocking._block_every_pair opens on the materialised paths) says that
    nobody's numpy made the pair ids."""
    if settings.get("blocking_rules"):
        return _build_virtual_plan(settings, table, n_left, chunk)
    profiling.count(keyless_rules=1)
    with profiling.span(
        "keyless_pairs", groups=1, chunks=0, host_built=0
    ) as sp:
        plan = _build_virtual_plan(settings, table, n_left, chunk)
        if plan is not None:
            sp.count(units=len(plan.rules[0].ua), pairs=plan.rules[0].total)
    return plan


def _build_virtual_plan(settings, table, n_left, chunk):
    chunk = chunk or CHUNK
    link_type = settings["link_type"]
    rules = settings.get("blocking_rules") or []
    keyless = not rules
    parsed_cols = []
    residuals: list[tuple[str | None, object]] = []
    res_ops: list[np.ndarray] = []
    res_idx: dict = {}
    res_aux: dict = {}
    for rule in rules:
        eq_pairs, residual = parse_blocking_rule(rule)
        sym_cols, asym, residual = _split_join_keys(eq_pairs, residual)
        if not sym_cols:
            # no symmetric key to group on (a lone l.a = r.b, or no
            # equality at all): host blocking handles it
            return None
        if asym:
            # fold asymmetric equality keys into this rule's residual:
            # candidates still group by the symmetric keys and the device
            # mask enforces the cross-column equality via joint-vocabulary
            # ranks — host blocking meanwhile uses its shared-vocabulary
            # hash join (blocking._key_codes_asym); the pair sets match
            from .derived_keys import asym_residual_src

            term = asym_residual_src(asym)
            residual = f"({residual}) & {term}" if residual else term
        join_cols = sym_cols
        res_fn = None
        if residual is not None:
            res_fn = compile_residual_device(
                table, residual, res_ops, res_idx, res_aux
            )
            if res_fn is None:
                return None
        parsed_cols.append(join_cols)
        residuals.append((residual, res_fn))
    if res_aux.get("numeric_used"):
        import jax

        if not jax.config.jax_enable_x64:
            import logging

            logging.getLogger("splink_tpu").warning(
                "device pair generation: a blocking residual contains "
                "numeric arithmetic, which evaluates in float32 on TPU "
                "(no f64) — a pair exactly on a threshold may land "
                "differently than the float64 host path. Set "
                "device_pair_generation='off' for bit-identical host "
                "blocking."
            )

    n = table.n_rows
    uid_codes = None
    if link_type in ("dedupe_only", "link_and_dedupe"):
        # link_and_dedupe is a self-join over the concatenated table with
        # (source, uid) as the ordering key; duplicate ordering keys mean
        # the strict l.key < r.key ordering drops equal-key pairs — dense
        # codes feed the device mask (None when keys are unique)
        ranks, _ = _uid_ranks(table, link_type)
        uid_codes = _uid_mask_codes(table, link_type)

    if keyless:
        parsed_cols, residuals = [None], [(None, None)]
    plans: list[RulePlan] = []
    codes_all = np.empty((len(parsed_cols), n), np.int32)
    for r, join_cols in enumerate(parsed_cols):
        codes = (
            np.zeros(n, np.int64) if keyless else _key_codes(table, join_cols)
        )
        codes_all[r] = codes.astype(np.int32)  # codes < n <= 2^31
        if link_type in ("dedupe_only", "link_and_dedupe"):
            rows = np.flatnonzero(codes >= 0).astype(np.int32)
            rows = rows[np.argsort(ranks[rows], kind="stable")]
            rows_sorted, _, starts, sizes = _sort_groups(codes, rows)
            units = _units_for_self_join(starts, sizes, chunk)
            if units is None:
                return None
            ua, la, ub, lb = units
        else:
            assert n_left is not None
            all_rows = np.arange(n, dtype=np.int32)
            lrows_in = all_rows[:n_left]
            rrows_in = all_rows[n_left:]
            lrows, lcodes, lstarts, lsizes = _sort_groups(
                codes, lrows_in[codes[lrows_in] >= 0]
            )
            rrows, rcodes, rstarts, rsizes = _sort_groups(
                codes, rrows_in[codes[rrows_in] >= 0]
            )
            common, li, ri = np.intersect1d(
                lcodes, rcodes, return_indices=True
            )
            # one order array: [left-sorted | right-sorted]; right unit
            # starts shift by len(lrows)
            rows_sorted = np.concatenate([lrows, rrows]).astype(np.int32)
            if len(common):
                units = _units_for_cross_join(
                    lstarts[li],
                    lsizes[li],
                    rstarts[ri] + len(lrows),
                    rsizes[ri],
                    chunk,
                )
                if units is None:
                    return None
                ua, la, ub, lb = units
            else:
                ua = la = ub = lb = np.zeros(0, np.int64)
        pc = _pair_counts(ua, la, ub, lb)
        plans.append(
            RulePlan(
                order=np.ascontiguousarray(rows_sorted, dtype=np.int32),
                ua=ua.astype(np.int32),
                la=la.astype(np.int32),
                ub=ub.astype(np.int32),
                lb=lb.astype(np.int32),
                pc=pc,
                residual=residuals[r][0],
                residual_fn=residuals[r][1],
            )
        )
    return VirtualPlan(
        rules=plans,
        codes=codes_all,
        uid_codes=uid_codes,
        n_candidates=sum(rp.total for rp in plans),
        res_ops=res_ops,
        table=table,
        chunk=chunk,
    )


# --------------------------------------------------------------------------
# Host-side decode (the test oracle)
# --------------------------------------------------------------------------


def decode_positions(plan: VirtualPlan, rule: int, q: np.ndarray,
                     compute_masked: bool = True):
    """(i, j, masked) for rule-relative pair positions q (int64, numpy).

    The host mirror of the device kernel (f64 sqrt is exact here) and the
    oracle it is tested against: the row pairs the virtual pattern kernel
    hands back and its masked sentinels must equal these, position for
    position. Nothing of the program's pair stream calls it — the stream
    takes the kernel's own pairs (linker._iter_pattern_triples) — so its
    cost is the tests'. ``compute_masked=False`` skips the masks (masked
    comes back None).
    """
    rp = plan.rules[rule]
    u = np.searchsorted(rp.pc, q, side="right") - 1
    t = q - rp.pc[u]
    A, LA = rp.ua[u].astype(np.int64), rp.la[u].astype(np.int64)
    Bs, LB = rp.ub[u].astype(np.int64), rp.lb[u].astype(np.int64)
    tri = A == Bs
    with np.errstate(invalid="ignore"):
        disc = (2 * LA - 1).astype(np.float64) ** 2 - 8 * t.astype(np.float64)
        a_t = np.floor(
            ((2 * LA - 1) - np.sqrt(np.maximum(disc, 0.0))) / 2
        ).astype(np.int64)
    off = lambda a: a * LA - (a * (a + 1)) // 2  # noqa: E731
    a_t = np.where(off(a_t + 1) <= t, a_t + 1, a_t)
    a_t = np.where(off(a_t) > t, a_t - 1, a_t)
    b_t = t - off(a_t) + a_t + 1
    lb_safe = np.maximum(LB, 1)
    a_r = t // lb_safe
    b_r = t - a_r * lb_safe
    a = np.where(tri, a_t, a_r)
    b = np.where(tri, b_t, b_r)
    i = rp.order[(A + a).astype(np.int64)]
    j = rp.order[(Bs + b).astype(np.int64)]
    if not compute_masked:
        return i, j, None
    masked = np.zeros(len(q), bool)
    if plan.uid_codes is not None:
        masked |= plan.uid_codes[i] == plan.uid_codes[j]
    if rp.residual is not None:
        from .residual_eval import evaluate_residual

        masked |= ~evaluate_residual(plan.table, rp.residual, i, j)
    for prev in range(rule):
        cp = plan.codes[prev]
        holds = (cp[i] == cp[j]) & (cp[i] >= 0)
        prev_res = plan.rules[prev].residual
        if prev_res is not None and holds.any():
            from .residual_eval import evaluate_residual

            sub = np.flatnonzero(holds)
            keep = evaluate_residual(plan.table, prev_res, i[sub], j[sub])
            holds = holds.copy()
            holds[sub] = keep
        masked |= holds
    return i, j, masked


# --------------------------------------------------------------------------
# Device kernel
# --------------------------------------------------------------------------


def make_virtual_pattern_fn(program, batch_size: int, n_prev: int,
                            has_uid_mask: bool, own_res=None,
                            prev_res=(), mesh=None):
    """Jitted (pid, i, j, acc) kernel decoding + scoring one batch of virtual
    pair positions; ``i`` / ``j`` are the row pairs it decoded (int32, one per
    position), always there and downloaded only by a pass that wants the
    ids. Shapes of the plan arrays vary per rule, so XLA
    compiles one executable per (rule shape, kpad bucket) — a handful per
    run. own_res / prev_res are compiled residual closures (traced into
    this jit; the ops arrays arrive as the res_ops argument).

    The kernel is the PROCESS's, not the caller's: it comes from the
    kernel registry under the program's signature plus everything else it
    closes over — ``n_prev``, ``has_uid_mask``, the mesh by value and the
    residuals' signatures (compile_residual_device) — so a
    second linker on the same model gets the same jitted function and
    builds nothing. A residual that carries no signature cannot be keyed:
    that kernel is the program's own.

    With ``mesh``, the batch SHARDS over the mesh's data axis: ``pos``
    arrives as a sharded iota (the only sharded input — plan arrays, table
    data and codes are replicated), every per-position op partitions
    trivially along it, the gamma body runs per shard
    (gammas._mesh_gamma_body), and XLA inserts one psum for the
    histogram accumulator. This is how the virtual pair index composes with
    multi-chip EM: each chip decodes and scores its own slice of every
    unit, the way the reference's Spark join distributed its shuffle
    partitions (/root/reference/splink/blocking.py:210)."""
    prev_res = tuple(prev_res)
    residuals = (own_res, *prev_res)
    signed = residual_signatures(residuals)
    variant = (
        n_prev, bool(has_uid_mask), mesh_key(mesh),
        # unsigned: the closures themselves, for the program's own memo
        residuals if signed is None else signed,
    )
    return program._kernel(
        "virtual_pattern", variant,
        functools.partial(
            _build_virtual_pattern_fn, n_prev=n_prev,
            has_uid_mask=has_uid_mask, own_res=own_res, prev_res=prev_res,
            mesh=mesh,
        ),
        shareable=signed is not None, mesh=mesh,
    )


def _build_virtual_pattern_fn(parts, n_prev, has_uid_mask, own_res,
                              prev_res, mesh):
    """The virtual pattern kernel from a gamma program's parts
    (gammas._Parts) — nothing of a linker, a plan or a table, which a
    registered kernel would pin."""
    import jax
    import jax.numpy as jnp

    from .gammas import _make_gamma_body, _mesh_gamma_body

    n_patterns = parts.n_patterns
    strides_dev = jnp.asarray(parts.strides, jnp.int32)
    # one gamma body, per shard under a mesh; acc layout: [patterns
    # 0..n_patterns-1, masked sentinel]
    if mesh is not None:
        gamma_fn = _mesh_gamma_body(parts, mesh)
    else:
        gamma_fn = _make_gamma_body(parts)

    jit_kwargs = {}
    if mesh is not None:
        from .parallel.mesh import pair_sharding, replicated

        # pid and the decoded row pairs come back sharded along the pair
        # axis; the histogram is the cross-shard psum and replicates
        pairs = pair_sharding(mesh)
        jit_kwargs = {
            "out_shardings": (pairs, pairs, pairs, replicated(mesh)),
        }

    # Named ``fn`` on purpose: the benchmark's gamma metrics match the XLA
    # module ``jit_fn(``, and its files are not this code's to edit. This
    # and gammas._jit_gamma_batch are the only two programs of that name,
    # so ``jit_fn(`` means the gamma body and nothing else.
    @functools.partial(jax.jit, **jit_kwargs)
    def fn(pos, packed, order, ua, la, ub, lb, prev_codes, uid_codes,
           res_ops, meta, acc):
        # meta packs this batch's scalars with its pc slice in ONE device
        # array — [u0, valid, pc_slice...] — uploaded per batch by the
        # driver with device_put (async on every backend measured; see
        # the driver-loop comment for why it must never be an eager
        # device-side slice of a preuploaded table instead).
        with jax.named_scope("pair_decode"):
            i, j, valid = unit_decode(
                pos, order, ua, la, ub, lb, meta, mesh_ladder=mesh is not None
            )

        masked = pos >= valid
        if has_uid_mask:
            masked = masked | (uid_codes[i] == uid_codes[j])
        if own_res is not None:
            v, unk = own_res(i, j, res_ops)
            masked = masked | ~(v & ~unk)
        for p in range(n_prev):
            cp = prev_codes[p]
            holds = (cp[i] == cp[j]) & (cp[i] >= 0)
            if prev_res and prev_res[p] is not None:
                v, unk = prev_res[p](i, j, res_ops)
                holds = holds & v & ~unk
            masked = masked | holds

        G = gamma_fn(packed, i, j).astype(jnp.int32)
        pid = jnp.sum(
            (G + 1) * strides_dev[None, :], axis=1, dtype=jnp.int32
        )
        pid = jnp.where(masked, n_patterns, pid)
        acc = acc + int32_histogram(pid, n_patterns + 1)
        if pattern_ids_fit_uint16(n_patterns):
            # narrow ON DEVICE: every value (sentinel included) fits
            # uint16 — half the D2H bytes of the int32 it was computed in
            pid = pid.astype(jnp.uint16)
        # the row pairs the ids were computed from go out beside them, so
        # that no caller decodes the positions a second time
        return pid, i, j, acc

    return fn


def _abstract_args(args):
    """The arguments of a kernel call as ShapeDtypeStructs, shardings kept."""
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        args,
    )


def compiled_kernel_texts(plan: VirtualPlan) -> list[tuple[int, str]]:
    """(batch size, optimised HLO) of every pattern kernel this plan has
    RUN, one per (rule, batch shape, mesh), re-lowered from the shapes and
    shardings of the kernel's first call. Diagnostics: how XLA partitioned
    the kernels of a run (collectives, what each device feeds the string
    kernels) — chip_smoke.py's mesh leg reads it."""
    return [
        (key[0], fn.lower(*args).compile().as_text())
        for rp in plan.rules
        for key, (fn, args) in rp.kernels_run.items()
    ]


def _virtual_pass_iter(program, plan: VirtualPlan, batch_size: int,
                       mesh=None, want_ids: bool = True, counts_out=None):
    """Drive one device pass over the virtual pair stream, yielding
    ``(rule, rule_p0, out_pos, n_valid, pid_host, il_host, ir_host)`` per
    batch: the pattern id of every position of the batch and the row pair
    the kernel decoded it to (int32; at a masked position, where
    ``pid_host`` holds the sentinel ``n_patterns``, the pair means nothing).
    With ``want_ids``, one pooled download a batch brings the three home, a
    few batches deep (yield order stays submission order), so downloads
    are not serialised on the driver thread (what the driver still waits
    is the ``d2h_wait`` span, 2.18 s of a ``c4_dedupe_virtual`` job; under
    a mesh ``mesh_put`` + ``mesh_gather`` add 0.60 s a job: ledger PR 30).
    The three are None when ``want_ids`` is
    False — then NOTHING of a batch crosses the link: what comes home is the
    int32 histogram accumulator every ~2^10 batches and at the end
    (``flush_acc``, where the driver waits for the pass's kernels).

    The innermost open stage gets the pass's counts: ``hist_flushes``, and
    ``redo_positions``, the constant 0 (no batch is ever run twice; the
    benchmark's ``stream_redo_positions`` reads it).

    The histogram accumulates into ``counts_out`` (int64, n_patterns); the
    caller owns the array. Host work per batch is O(units-in-batch): a
    searchsorted plus an int32 slice of the unit cumulative table.
    """
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from .gammas import _HIST_FLUSH_BATCHES
    from .utils.profiling import (
        count,
        dispatched,
        fetch,
        fetch_pooled,
        poll,
        span,
    )

    n_patterns = program.n_patterns
    total = plan.n_candidates
    counts = counts_out if counts_out is not None else np.zeros(
        n_patterns, np.int64
    )
    if total == 0:
        return
    # int32-safe bound: the device kernel reads batch-relative positions in
    # int32, and pc_rel below can exceed the batch end by up to one unit's
    # pair count (CHUNK^2) — an unbounded settings pair_batch_size near 2^31
    # must clamp here, not silently corrupt the unit decode (np.clip alone
    # would wrap positions INSIDE the batch)
    # margin from the plan's ACTUAL unit extent, not the module default —
    # a plan built with a larger chunk has larger pc_rel overshoot
    safe = (1 << 31) - 1 - plan.chunk * plan.chunk
    batch_size = min(batch_size, max(total, 1), safe)
    if mesh is not None:
        from .parallel.mesh import (
            gather_from_mesh,
            pad_to_multiple,
            pair_sharding,
            put_on_mesh,
            replicated,
        )

        # the sharded iota splits evenly over the mesh; positions past
        # `valid` carry the sentinel and drop like any masked position.
        # Padding must not push back above the int32-safe bound the clamp
        # just enforced — round DOWN to a mesh multiple in that case
        msz = mesh.devices.size
        batch_size = pad_to_multiple(batch_size, msz)
        if batch_size > safe:
            batch_size = max(safe // msz, 1) * msz
        shard = pair_sharding(mesh)
        repl = replicated(mesh)
        put = lambda a: jax.device_put(a, repl)  # noqa: E731
        place = functools.partial(put_on_mesh, repl)
        download = gather_from_mesh
        on_mesh = {"devices": msz}
    else:
        put = jnp.asarray
        download = np.asarray
        on_mesh = {}

        def place(*arrays):
            """The pass's table and plan arrays onto the device; under a
            mesh, parallel.mesh.put_on_mesh (span ``mesh_put``)."""
            with span("h2d_put", bytes=sum(a.nbytes for a in arrays)):
                return tuple(jnp.asarray(a) for a in arrays)
    # per-bucket iota cache: rules sharing a rule_bs bucket share one array
    pos_cache: dict = {}
    flush_every = max(min(_HIST_FLUSH_BATCHES, (1 << 30) // batch_size), 1)
    # acc carries [histogram, masked sentinel]
    acc = put(np.zeros(n_patterns + 1, np.int32))
    in_acc = 0
    count(redo_positions=0, hist_flushes=0)

    def flush_acc(acc_dev):
        counts[:] += fetch(acc_dev)[:n_patterns]
        count(hist_flushes=1)

    def download_batch(outs):
        """One batch's pattern ids and row pairs home, on a pool thread."""
        home = tuple(download(x) for x in outs)
        poll()  # the batch's program has ended: its device record closes
        return home

    def settle(entry):
        """What the pass yields for the oldest batch in flight, once its
        download is home: the driver thread's D2H wait."""
        pr, pp0, ps, n_valid, fut = entry
        home = fetch_pooled(fut)
        return (pr, pp0, ps, n_valid, *(a[:n_valid] for a in home))

    pool = ThreadPoolExecutor(max_workers=_D2H_DEPTH) if want_ids else None
    # (rule, rule_p0, out_pos, n_valid, future)
    inflight: deque = deque()
    try:
        packed = program._packed
        uid_codes = (
            plan.uid_codes if plan.uid_codes is not None
            else np.zeros(1, np.int32)
        )
        # all rules' codes and residual operand arrays upload ONCE (the
        # kernel's static n_prev bounds how many code rows it reads); per-rule
        # plan arrays + kernel are built per rule (shapes differ, so each rule
        # is its own jit specialisation)
        if mesh is not None:
            (packed,) = place(packed)  # from the program's device to all
        uid_dev, codes_dev, *res_ops_dev = place(
            uid_codes, plan.codes, *plan.res_ops
        )
        res_ops_dev = tuple(res_ops_dev)
        out_pos = 0
        for r, rp in enumerate(plan.rules):
            if rp.total == 0:
                continue
            # clamp the batch to this RULE's total (power-of-two bucket so jit
            # specialisations stay bounded): a 38k-pair rule must not run a
            # full pair_batch_size of padded lanes — with many small rules the
            # padding waste would dominate the whole pass. rule_bs <= batch_size
            # always, so the int32-safety clamp above still covers it (under a
            # mesh, batch_size is already a mesh multiple, so padding rule_bs
            # cannot exceed it)
            rule_bs = min(batch_size, 1 << max((rp.total - 1).bit_length(), 6))
            if mesh is not None:
                rule_bs = pad_to_multiple(rule_bs, mesh.devices.size)
            pos_rule = pos_cache.get(rule_bs)
            if pos_rule is None:
                if mesh is not None:
                    (pos_rule,) = put_on_mesh(
                        shard, np.arange(rule_bs, dtype=np.int32)
                    )
                else:
                    pos_rule = jnp.arange(rule_bs, dtype=jnp.int32)
                pos_cache[rule_bs] = pos_rule
            order_dev, *units_dev = place(rp.order, rp.ua, rp.la, rp.ub, rp.lb)
            units_dev = tuple(units_dev)
            # the program hands out the process's kernel for this rule
            # (make_virtual_pattern_fn): the same jitted function in every
            # pass and every linker on this model
            fn = make_virtual_pattern_fn(
                program, rule_bs, n_prev=r,
                has_uid_mask=plan.uid_codes is not None,
                own_res=rp.residual_fn,
                prev_res=tuple(p.residual_fn for p in plan.rules[:r]),
                mesh=mesh,
            )
            kkey = (rule_bs, mesh_key(mesh))
            # One metadata row per batch (_unit_batch_meta), uploaded per
            # batch with device_put — uploads are ASYNC, where an EAGER
            # device-side op like meta_dev[b] is a blocking dispatch;
            # never slice eagerly in this loop.
            for p0, p1, meta in _unit_batch_meta(rp.pc, rp.total, rule_bs):
                meta_dev = put(meta)
                args = (
                    pos_rule, packed, order_dev, *units_dev, codes_dev,
                    uid_dev, res_ops_dev, meta_dev, acc,
                )
                if kkey not in rp.kernels_run:
                    rp.kernels_run[kkey] = (fn, _abstract_args(args))
                pid, i, j, acc = fn(*args)
                # polled by the accumulator: a few words, and the next
                # batch's input, so the record pins nothing of the batch
                dispatched("fn", acc, positions=p1 - p0, **on_mesh)
                if want_ids:
                    inflight.append(
                        (r, p0, out_pos, p1 - p0,
                         pool.submit(download_batch, (pid, i, j)))
                    )
                    while len(inflight) > _D2H_DEPTH:
                        yield settle(inflight.popleft())
                else:
                    # nothing of the batch comes home: its ids and row
                    # pairs are let go with the call
                    yield r, p0, out_pos, p1 - p0, None, None, None
                out_pos += p1 - p0
                in_acc += 1
                if in_acc >= flush_every:
                    flush_acc(acc)
                    # reset through put(): a plain jnp.zeros would drop the
                    # replicated sharding under a mesh and force a reshard /
                    # second executable on the next batch
                    acc = put(np.zeros(n_patterns + 1, np.int32))
                    in_acc = 0
        while inflight:
            yield settle(inflight.popleft())
        if in_acc:
            flush_acc(acc)
    finally:
        # consumer may abandon the generator mid-stream (exception in
        # a scoring chunk): do not leak pool threads or pinned buffers
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)


class VirtualIds(NamedTuple):
    """What a virtual pass keeps per candidate position, in the pass's order
    (rule after rule): the pattern id (the sentinel ``n_patterns`` where the
    position is masked) and the row pair the kernel decoded it to."""

    pid: np.ndarray  # uint16 where the sentinel fits, else int32
    il: np.ndarray  # int32
    ir: np.ndarray  # int32


def compute_virtual_pattern_ids(program, plan: VirtualPlan,
                                batch_size: int, mesh=None,
                                return_ids: bool = True):
    """One device pass over the VIRTUAL pair stream: (ids, counts,
    n_real). ``ids`` is a ``VirtualIds``: per candidate position the pattern
    id — the sentinel value ``n_patterns`` for masked (deduped) positions —
    and the row pair ``il`` / ``ir`` the kernel decoded the position to, so
    that whoever keeps the ids never decodes a position again; counts
    excludes the masked positions; n_real = counts.sum().

    With ``return_ids=False`` the pass computes ONLY the histogram — ids
    comes back None and no per-pair bytes ever cross the host<->device
    link. This is the EM-path mode: EM needs nothing but counts (what the
    per-batch download costs is the ``d2h_wait`` / ``mesh_gather`` spans
    of a ``chipbench`` run). The
    score-output stream recomputes ids and pairs chunk-wise later via
    ``_virtual_pass_iter`` (the kernels are the process's, so the second
    pass pays no compile).

    With ``mesh``, each batch SHARDS over the mesh's data axis (see
    make_virtual_pattern_fn) — bit-identical output to the single-device
    pass, with per-chip work divided by the mesh size.
    """
    n_patterns = program.n_patterns
    # sentinel must be representable
    id_dtype = np.uint16 if pattern_ids_fit_uint16(n_patterns) else np.int32
    counts = np.zeros(n_patterns, np.int64)
    ids = VirtualIds(
        np.empty(plan.n_candidates, id_dtype),
        np.empty(plan.n_candidates, np.int32),
        np.empty(plan.n_candidates, np.int32),
    ) if return_ids else None
    from .utils.profiling import count

    for _, _, ps, n_valid, *chunks in _virtual_pass_iter(
        program, plan, batch_size, mesh=mesh, want_ids=return_ids,
        counts_out=counts,
    ):
        count(batches=1)
        if return_ids:
            for kept, chunk in zip(ids, chunks):
                kept[ps : ps + n_valid] = chunk
    return ids, counts, int(counts.sum())
