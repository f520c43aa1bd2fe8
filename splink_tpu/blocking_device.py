"""Device-native blocking: on-device hash-join candidate generation.

blocking.py's host joins were the last pipeline stage computed entirely on
the host — np.argsort over every rule's key codes, np.repeat/np.cumsum pair
expansion, single-threaded (8.2M pairs/s on one CPU core against 28M+/s
in a builders' chip session of round 4, older than the code: neither
reproduced). This module moves the join itself onto the device as a
sort-based hash join over the SAME packed key codes blocking.py builds
(HyperBlocker, arXiv:2410.04349, maps rule-based blocking onto exactly this
kind of accelerator parallelism):

  1. segmented sort — one ``lax.sort`` of ``(key_code, side, rank)``
     carrying row ids: equal keys become contiguous segments, group members
     arrive pre-sorted by uid rank (orientation comes out of the join for
     free, `_self_join`'s trick), and the two sides of a link / asymmetric
     join interleave as (code, side) runs;
  2. run-length segment detection — boundary flags + a pinned int32 cumsum
     give each position its segment id; per-segment starts and per-side
     extents compact through scatter-min/scatter-add. Only this compact
     O(segments) table crosses back to the host;
  3. pair expansion — the host splits segments into the SAME bounded
     triangle/rectangle units as the virtual pair index (pairgen's f32-exact
     decode, reused verbatim via ``pairgen.unit_decode``) and the emission
     kernel decodes each chunk of global pair positions into (i, j) row
     pairs ON DEVICE, applies the sequential-rule dedup mask (earlier-rule
     key equality + compiled residuals — the reference's ``AND NOT
     ifnull(prev, false)`` — mirroring pairgen's mask semantics), the
     duplicate-uid mask and the asymmetric-rule rank orientation filter,
     then compacts survivors with an int32 rank-scatter;
  4. chunked emission under an explicit pair budget
     (``blocking_chunk_pairs``) — a huge block streams as fixed-shape
     chunks instead of OOMing, the Progressive-Blocking shape
     (arXiv:2005.14326) of emitting candidates under a budget rather than
     all-at-once. Chunk shapes are power-of-two stable, so steady-state
     emission never recompiles.

An EMPTY rule list — the reference compares every pair of rows then — is
the limiting case of a hot key and takes the same road: ONE keyless group of
all rows (a constant key code, which the segment sort orders by uid rank like
any key), tiled into the same units and emitted in the same budgeted chunks;
blocking._block_every_pair's ``keyless_pairs`` span says who built the pair
ids (``host_built`` 0 here: nobody's numpy).

The host path in blocking.py is retained as the fallback (a keyless rule
WITH a residual, residuals the device compiler rejects, a group past
MAX_UNITS_PER_GROUP, >=2^31 key codes; the CPU backend under "auto") and as
the parity oracle: the device pair set is
bit-equal AS A SET to the host pair set on every supported shape
(tests/test_blocking_device.py; ``make blocking-smoke`` gates it).

serve/index.py reuses the segmented sort through :func:`build_bucket_csr`
to build its per-rule bucket CSR (rows_sorted/starts/sizes/row_bucket) on
device instead of the host argsort.

All kernels are registered in the three audit layers: jaxlint (AST),
trace_audit (``block_segment_sort``, ``block_bucket_csr``,
``block_pair_emit`` — x64-forced dtype/const/callback/determinism budgets)
and shard_audit (``block_pair_decode_sharded`` — the decode+mask body is
embarrassingly parallel over positions and lowers collective-free with
sharded outputs; the compaction cumsum is single-device by design, the
host compacts per shard).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass, field

import numpy as np

from .blocking import (
    _key_codes,
    _key_codes_asym,
    _split_join_keys,
    _uid_ranks,
    parse_blocking_rule,
)
from .data import EncodedTable
from .pairgen import (
    CHUNK,
    _pair_counts,
    _uid_mask_codes,
    _unit_batch_meta,
    _units_for_cross_join,
    _units_for_self_join,
    compile_residual_device,
    residual_signatures,
    unit_decode,
)
from .utils import kernel_registry
from .utils.kernel_registry import mesh_key
from .utils.profiling import count_here, dispatched, fetch, fetch_pooled, poll

logger = logging.getLogger("splink_tpu")

# Default emission chunk (pairs per device batch) when the settings carry no
# blocking_chunk_pairs; also the schema default. Bounds the transient device
# footprint of one chunk (~9 int32 lanes x chunk) and the host RAM of one
# downloaded chunk.
DEFAULT_CHUNK_PAIRS = 1 << 22

# "auto" mode engages the device tier only past this estimated pair count:
# below it the host join finishes in milliseconds and the jit warmup would
# dominate (the same shape as device_pair_generation's auto gate).
AUTO_MIN_PAIRS = 1 << 21

# Concurrent chunk downloads in flight (pairgen._D2H_DEPTH rationale: D2H
# round trips overlap the next chunk's kernel instead of serialising it).
_D2H_DEPTH = 2

_IMAX = np.iinfo(np.int32).max

# The one rule of a plan built from an EMPTY rule list: every pair of rows
# (link_only: every left x right pair), as the reference compares them.
KEYLESS_RULE = "<no blocking rule: every pair>"


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the shape-bucketing that keeps
    jit specialisations shared across tables of similar size."""
    return 1 << max(int(n) - 1, 0).bit_length()


# --------------------------------------------------------------------------
# Kernels
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def make_segment_sort_fn():
    """Jitted segmented sort + run-length segment detection.

    fn(codes, side, rank, row) ->
        (rows_sorted, seg_start, l_cnt, r_cnt, n_seg, n_valid)

    Entries sort by (key, side, rank) with null keys (code < 0 — including
    the power-of-two padding) remapped to int32 max so they collapse into
    one trailing segment the host drops (``seg_start >= n_valid``). Segment
    starts compact via scatter-min over the per-position segment id,
    per-side extents via scatter-add — all shapes static, all dtypes pinned
    int32 (the TPU production width; x64 audit tier traces identically).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def block_segment_sort(codes, side, rank, row):
        m = codes.shape[0]
        imax = jnp.int32(_IMAX)
        key = jnp.where(codes < 0, imax, codes)
        key_s, side_s, _, row_s = lax.sort(
            (key, side, rank, row), num_keys=3, is_stable=True
        )
        n_valid = jnp.sum((codes >= 0).astype(jnp.int32), dtype=jnp.int32)
        pos = jnp.arange(m, dtype=jnp.int32)
        boundary = jnp.concatenate(
            [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
        )
        seg_of = jnp.cumsum(boundary.astype(jnp.int32), dtype=jnp.int32) - 1
        # n_seg as a reduction, NOT seg_of[-1]: a traced negative index
        # lowers through an int64 dynamic_slice under x64 (TA-DTYPE)
        n_seg = jnp.sum(boundary.astype(jnp.int32), dtype=jnp.int32)
        seg_start = jnp.full(m, imax, jnp.int32).at[seg_of].min(pos)
        l_cnt = (
            jnp.zeros(m, jnp.int32)
            .at[seg_of]
            .add((side_s == 0).astype(jnp.int32))
        )
        r_cnt = (
            jnp.zeros(m, jnp.int32)
            .at[seg_of]
            .add((side_s == 1).astype(jnp.int32))
        )
        return row_s, seg_start, l_cnt, r_cnt, n_seg, n_valid

    return block_segment_sort


@functools.lru_cache(maxsize=1)
def make_bucket_csr_fn():
    """Jitted bucket-CSR build for the serving index: fn(codes) ->
    (rows_sorted, starts, sizes, row_bucket, n_seg, n_valid), bit-equal to
    the host ``blocking._sort_groups`` construction (stable sort keeps rows
    ascending within a bucket; buckets ordered by ascending key code).
    row_bucket is -1 for null-key rows, exactly the serving contract."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def block_bucket_csr(codes):
        m = codes.shape[0]
        imax = jnp.int32(_IMAX)
        key = jnp.where(codes < 0, imax, codes)
        rows = jnp.arange(m, dtype=jnp.int32)
        key_s, row_s = lax.sort((key, rows), num_keys=1, is_stable=True)
        n_valid = jnp.sum((codes >= 0).astype(jnp.int32), dtype=jnp.int32)
        boundary = jnp.concatenate(
            [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
        )
        seg_of = jnp.cumsum(boundary.astype(jnp.int32), dtype=jnp.int32) - 1
        # reduction, not seg_of[-1] (int64 dynamic_slice under x64)
        n_seg = jnp.sum(boundary.astype(jnp.int32), dtype=jnp.int32)
        starts = jnp.full(m, imax, jnp.int32).at[seg_of].min(rows)
        sizes = jnp.zeros(m, jnp.int32).at[seg_of].add(jnp.int32(1))
        valid_entry = rows < n_valid
        dest = jnp.where(valid_entry, row_s, m)
        row_bucket = (
            jnp.full(m, -1, jnp.int32).at[dest].set(seg_of, mode="drop")
        )
        return row_s, starts, sizes, row_bucket, n_seg, n_valid

    return block_bucket_csr


def make_pair_emit_fn(batch_size: int, n_prev: int, has_uid_mask: bool,
                      rank_filter: bool, own_res=None, prev_res=(),
                      mesh=None, compact: bool = True):
    """Jitted emission kernel: decode one chunk of global pair positions
    into (i, j) row pairs and compact the survivors.

    Composes pairgen's ``unit_decode`` (the same f32-exact
    triangle/rectangle math the virtual pattern kernel runs), then masks:
    tail padding (``pos >= valid``), the asymmetric-rule rank orientation
    filter (``rank[i] < rank[j]`` — the reference's l.key < r.key on a
    cross join of the table against itself), the duplicate-uid drop, the
    rule's own residual and every EARLIER rule's predicate (key equality on
    that rule's l/r codes AND its residual, UNKNOWN counting as
    not-produced — blocking._rule_holds semantics). Survivors compact via
    an int32 rank-scatter (cumsum of the keep mask), so the host downloads
    ``count`` real pairs in the first ``count`` lanes.

    With ``mesh`` the kernel returns the UNCOMPACTED (i, j, keep) triple
    sharded along the position axis — compaction is a prefix sum, which
    would force cross-shard comms; each shard's survivors compact host-side
    instead. The sharded body is collective-free (shard_audit pins it).

    ``compact=False`` returns the same uncompacted triple on a single
    device: XLA's CPU scatter lowering is a serial loop (measured ~4x the
    whole decode for a 4M chunk), so the CPU-backend driver compacts
    host-side with vectorised numpy instead — on accelerator backends the
    on-device compaction stands: it halves the D2H bytes (whether D2H is
    the scarce resource there is not measured on this machine).

    The kernel closes over its arguments and nothing else, so it is the
    PROCESS's (utils/kernel_registry), keyed by them — the mesh by value,
    each residual by its signature (pairgen.compile_residual_device): a
    second linker's plan gets the same jitted function and builds nothing.
    A residual without a signature cannot be keyed; that kernel is built
    for its caller alone.
    """
    prev_res = tuple(prev_res)
    signed = residual_signatures((own_res, *prev_res))
    key = None if signed is None else (
        "pair_emit", batch_size, n_prev, bool(has_uid_mask),
        bool(rank_filter), mesh_key(mesh), bool(compact), signed,
    )
    return kernel_registry.lookup(
        "pair_emit", key,
        functools.partial(
            _build_pair_emit_fn, batch_size, n_prev, has_uid_mask,
            rank_filter, own_res, prev_res, mesh, compact,
        ),
    )


def _build_pair_emit_fn(batch_size, n_prev, has_uid_mask, rank_filter,
                        own_res, prev_res, mesh, compact):
    import jax
    import jax.numpy as jnp

    jit_kwargs = {}
    if mesh is not None:
        from .parallel.mesh import pair_sharding

        shard = pair_sharding(mesh)
        jit_kwargs = {"out_shardings": (shard, shard, shard)}

    # a kernel with NO mask terms needs no keep vector at all: the only
    # dropped positions are the tail past `valid`, and the DRIVER knows
    # valid (it built the meta row) — it slices the download instead. This
    # skips the keep compute, its D2H and the host compress for every
    # maskless rule (typically the first, largest rule of a run).
    maskless = (
        mesh is None
        and n_prev == 0
        and not has_uid_mask
        and not rank_filter
        and own_res is None
    )

    @functools.partial(jax.jit, **jit_kwargs)
    def block_pair_emit(pos, order, ua, la, ub, lb, ranks, prev_l, prev_r,
                        uid_codes, res_ops, meta):
        i, j, valid = unit_decode(
            pos, order, ua, la, ub, lb, meta, mesh_ladder=mesh is not None
        )
        if maskless:
            return i, j, None
        keep = pos < valid
        if rank_filter:
            keep = keep & (ranks[i] < ranks[j])
        if has_uid_mask:
            keep = keep & (uid_codes[i] != uid_codes[j])
        if own_res is not None:
            v, unk = own_res(i, j, res_ops)
            keep = keep & v & ~unk
        for p in range(n_prev):
            cl = prev_l[p]
            cr = prev_r[p]
            holds = (cl[i] == cr[j]) & (cl[i] >= 0)
            if prev_res and prev_res[p] is not None:
                v, unk = prev_res[p](i, j, res_ops)
                holds = holds & v & ~unk
            keep = keep & ~holds
        if mesh is not None or not compact:
            return i, j, keep
        kcum = jnp.cumsum(keep.astype(jnp.int32), dtype=jnp.int32)
        dest = jnp.where(keep, kcum - 1, jnp.int32(batch_size))
        out_i = jnp.zeros(batch_size, jnp.int32).at[dest].set(i, mode="drop")
        out_j = jnp.zeros(batch_size, jnp.int32).at[dest].set(j, mode="drop")
        # count rides as the last lane of a (batch_size + 1,) array so one
        # download carries pairs AND count
        out_i = jnp.concatenate([out_i, kcum[-1:]])
        return out_i, out_j, keep

    return block_pair_emit


# --------------------------------------------------------------------------
# Plan build (host: key codes -> device sort -> bounded units)
# --------------------------------------------------------------------------


@dataclass
class DeviceRule:
    """One rule's device join structure."""

    rule: str
    order: np.ndarray  # (M,) int32 pow2-padded sorted entry rows
    ua: np.ndarray  # (U,) int32 unit a-side start into `order`
    la: np.ndarray  # (U,) int32 a-side extent (<= chunk)
    ub: np.ndarray  # (U,) int32 b-side start (== ua for triangles)
    lb: np.ndarray  # (U,) int32 b-side extent
    pc: np.ndarray  # (U+1,) int64 cumulative pair counts
    rank_filter: bool  # asymmetric self-join: keep rank[i] < rank[j]
    residual: str | None = None
    residual_fn: object = None

    @property
    def total(self) -> int:
        return int(self.pc[-1]) if len(self.pc) else 0


@dataclass
class DeviceBlockPlan:
    rules: list[DeviceRule]
    codes_l: np.ndarray  # (R, n) int32 per-rule l-side codes (dedup mask)
    codes_r: np.ndarray  # (R, n) int32 r-side codes (== l row when symmetric)
    ranks: np.ndarray  # (n,) int32 uid ranks (zeros for link_only)
    uid_codes: np.ndarray | None  # (n,) int32 when duplicate uids exist
    res_ops: list[np.ndarray] = field(default_factory=list)
    chunk: int = CHUNK  # unit extent bound (int32/f32-exactness margin)
    # this plan's emission kernels by (rule, batch, mesh, compaction): its
    # view of the process's registry (make_pair_emit_fn), asked once a key
    kernel_cache: dict = field(default_factory=dict)

    @property
    def n_candidates(self) -> int:
        return sum(rp.total for rp in self.rules)


def build_device_plan(
    settings: dict, table: EncodedTable, n_left: int | None = None,
    chunk: int | None = None,
) -> DeviceBlockPlan | None:
    """Build the device join plan, or None when a rule needs the host path
    (a keyless rule with a residual, an uncompilable residual, >=2^31 key
    codes, or a group exceeding the per-group unit cap).

    An EMPTY rule list (the reference compares every pair then) is the one
    KEYLESS rule: ONE group holding every row — a constant key code, sorted
    and tiled into the same bounded triangles / rectangles as any hot key
    (10,000 rows = 5 chunks = 15 units)."""
    chunk = chunk or CHUNK
    link_type = settings["link_type"]
    rules = settings.get("blocking_rules") or []
    if table.n_rows == 0:
        return None
    n = table.n_rows
    if link_type == "link_only":
        assert n_left is not None
        ranks = np.zeros(n, np.int32)  # orientation fixed by construction
        uid_codes = None
    else:
        ranks, _ = _uid_ranks(table, link_type)
        uid_codes = _uid_mask_codes(table, link_type)

    res_ops: list[np.ndarray] = []
    res_idx: dict = {}
    res_aux: dict = {}
    parsed = []
    if not rules:
        zeros = np.zeros(n, np.int64)  # one group, all rows
        parsed.append((zeros, zeros, False, None, None))
    for rule in rules:
        eq_pairs, residual = parse_blocking_rule(rule)
        sym, asym, residual = _split_join_keys(eq_pairs, residual)
        if not sym and not asym:
            return None  # every pair against a residual: host path
        if asym:
            codes_l, codes_r = _key_codes_asym(table, sym, asym)
        else:
            codes_l = codes_r = _key_codes(table, sym)
        if len(codes_l) and (
            int(codes_l.max()) >= _IMAX or int(codes_r.max()) >= _IMAX
        ):
            return None  # codes must fit the int32 device lanes
        res_fn = None
        if residual is not None:
            res_fn = compile_residual_device(
                table, residual, res_ops, res_idx, res_aux
            )
            if res_fn is None:
                return None
        parsed.append((codes_l, codes_r, bool(asym), residual, res_fn))
    if res_aux.get("numeric_used"):
        import jax

        if not jax.config.jax_enable_x64:
            logger.warning(
                "device blocking: a blocking residual contains numeric "
                "arithmetic, which evaluates in float32 on TPU (no f64) — "
                "a pair exactly on a threshold may land differently than "
                "the float64 host path. Set device_blocking='off' for "
                "bit-identical host blocking."
            )

    sort_fn = make_segment_sort_fn()
    all_rows = np.arange(n, dtype=np.int32)
    plans: list[DeviceRule] = []
    rules = rules or [KEYLESS_RULE]
    codes_l_all = np.empty((len(rules), n), np.int32)
    codes_r_all = np.empty((len(rules), n), np.int32)
    for r, (codes_l, codes_r, is_asym, residual, res_fn) in enumerate(parsed):
        codes_l_all[r] = codes_l.astype(np.int32)
        codes_r_all[r] = codes_r.astype(np.int32)
        rank_filter = False
        if link_type == "link_only":
            # left input rows read the l-side codes, right rows the r-side
            # (identical arrays for a symmetric key); rectangles by
            # construction keep the left input on the l side
            ent_codes = np.concatenate(
                [codes_l_all[r][:n_left], codes_r_all[r][n_left:]]
            )
            ent_side = np.zeros(n, np.int32)
            ent_side[n_left:] = 1
            ent_rank = np.zeros(n, np.int32)
            ent_rows = all_rows
            triangle = False
        elif is_asym:
            # f(l) = g(r) over one table: every row enters once per side;
            # the reference's cross join of the table against itself with
            # the l.key < r.key where-condition — here the rank filter mask
            ent_codes = np.concatenate([codes_l_all[r], codes_r_all[r]])
            ent_side = np.concatenate(
                [np.zeros(n, np.int32), np.ones(n, np.int32)]
            )
            ent_rank = np.concatenate([ranks, ranks]).astype(np.int32)
            ent_rows = np.concatenate([all_rows, all_rows])
            triangle = False
            rank_filter = True
        else:
            # symmetric self-join: rank is the sort's tertiary key, so the
            # triangle decode's a < b IS rank_i < rank_j (ranks are a
            # permutation — duplicates only among uid COLLISIONS, which the
            # uid mask drops)
            ent_codes = codes_l_all[r]
            ent_side = np.zeros(n, np.int32)
            ent_rank = ranks.astype(np.int32)
            ent_rows = all_rows
            triangle = True
        m0 = len(ent_codes)
        m = _pow2(m0)
        if m != m0:  # pad with null keys: they join the dropped segment
            pad = m - m0
            ent_codes = np.concatenate(
                [ent_codes, np.full(pad, -1, np.int32)]
            )
            ent_side = np.concatenate([ent_side, np.zeros(pad, np.int32)])
            ent_rank = np.concatenate([ent_rank, np.zeros(pad, np.int32)])
            ent_rows = np.concatenate([ent_rows, np.zeros(pad, np.int32)])
        sorted_dev = sort_fn(ent_codes, ent_side, ent_rank, ent_rows)
        dispatched("block_segment_sort", sorted_dev[-1], rows=m)
        order, seg_start, l_cnt, r_cnt, n_seg, n_valid = fetch(sorted_dev)
        n_seg_h = int(n_seg)
        n_valid_h = int(n_valid)
        starts = seg_start[:n_seg_h].astype(np.int64)
        lz = l_cnt[:n_seg_h].astype(np.int64)
        rz = r_cnt[:n_seg_h].astype(np.int64)
        live = starts < n_valid_h  # drop the trailing null/pad segment
        starts, lz, rz = starts[live], lz[live], rz[live]
        if triangle:
            units = _units_for_self_join(starts, lz, chunk)
        else:
            both = (lz > 0) & (rz > 0)
            units = _units_for_cross_join(
                starts[both], lz[both], starts[both] + lz[both], rz[both],
                chunk,
            )
        if units is None:
            return None  # monster group: host blocking is the right tool
        ua, la, ub, lb = units
        plans.append(
            DeviceRule(
                rule=rules[r],
                order=np.ascontiguousarray(order, dtype=np.int32),
                ua=ua.astype(np.int32),
                la=la.astype(np.int32),
                ub=ub.astype(np.int32),
                lb=lb.astype(np.int32),
                pc=_pair_counts(ua, la, ub, lb),
                rank_filter=rank_filter,
                residual=residual,
                residual_fn=res_fn,
            )
        )
    return DeviceBlockPlan(
        rules=plans,
        codes_l=codes_l_all,
        codes_r=codes_r_all,
        ranks=np.ascontiguousarray(ranks, dtype=np.int32),
        uid_codes=uid_codes,
        res_ops=res_ops,
        chunk=chunk,
    )


# --------------------------------------------------------------------------
# Chunked emission
# --------------------------------------------------------------------------


def _chunk_home(out_i, out_j, keep, n_valid, compact_dev, sharded, own):
    """One emitted chunk's surviving pairs on the host, ``(i, j)``: the
    download both emission loops run on their pool threads. Where the
    survivors are a prefix of the downloaded buffers the slice VIEWS go
    through ``own(view, lanes)``; a boolean selection already copies."""
    ih, jh = np.asarray(out_i), np.asarray(out_j)
    poll()  # the chunk's program has ended: its device record closes
    if keep is None:  # maskless kernel: only the tail drops
        n = n_valid
    elif compact_dev:  # compacted on the device, the count in the last lane
        n = int(ih[-1])
    else:
        # compacted here. Under a mesh padded tail positions carry
        # keep=False; on one device rule overlap is rare, so most chunks
        # keep everything: detect that and hand out the prefix
        kh = np.asarray(keep) if sharded else np.asarray(keep)[:n_valid]
        if sharded or not kh.all():
            return ih[: len(kh)][kh], jh[: len(kh)][kh]
        n = n_valid
    return own(ih[:n], len(ih)), own(jh[:n], len(jh))


def _emission_context(plan: DeviceBlockPlan, batch_size: int, mesh):
    """Shared device setup for the TWO emission drivers
    (:func:`iter_device_pairs` — streaming — and
    :func:`emit_pairs_sharded` — the spill write path): the int32-safe
    batch clamp, mesh padding, the replicated put, the
    compaction-placement decision and the plan-constant uploads. One
    implementation, because the drivers are documented pair-set twins and
    a one-sided change to any of these invariants would silently diverge
    them."""
    import jax
    import jax.numpy as jnp

    # int32-safe bound, same margin as pairgen: batch-relative pc entries
    # can overshoot the batch end by up to one unit's pair count
    safe = (1 << 31) - 1 - plan.chunk * plan.chunk
    batch_size = min(max(int(batch_size), 64), safe)
    shard = None
    if mesh is not None:
        from .parallel.mesh import (
            pad_to_multiple,
            pair_sharding,
            replicated,
        )

        msz = mesh.devices.size
        batch_size = pad_to_multiple(batch_size, msz)
        if batch_size > safe:
            batch_size = max(safe // msz, 1) * msz
        shard = pair_sharding(mesh)
        repl = replicated(mesh)
        put = lambda a: jax.device_put(jnp.asarray(a), repl)  # noqa: E731
    else:
        put = jnp.asarray
    # on-device compaction only where it pays: it saves D2H bytes on
    # accelerator links but runs as a serial scatter loop on the XLA CPU
    # backend (make_pair_emit_fn docstring) — there the host compacts
    compact_dev = mesh is None and jax.default_backend() != "cpu"
    return {
        "batch_size": batch_size,
        "put": put,
        "shard": shard,
        "compact_dev": compact_dev,
        "on_mesh": {} if mesh is None else {"devices": mesh.devices.size},
        "ranks": put(plan.ranks),
        "codes_l": put(
            plan.codes_l if len(plan.codes_l) else np.zeros((1, 1), np.int32)
        ),
        "codes_r": put(
            plan.codes_r if len(plan.codes_r) else np.zeros((1, 1), np.int32)
        ),
        "uid": put(
            plan.uid_codes if plan.uid_codes is not None
            else np.zeros(1, np.int32)
        ),
        "res_ops": tuple(put(a) for a in plan.res_ops),
    }


def _rule_emit_setup(plan, r, rp, ctx, mesh, pos_cache):
    """Per-rule shared setup for both drivers: the pow2-clamped rule batch
    (mesh-padded), the cached position iota, the uploaded plan arrays and
    the cached emission kernel (one specialisation per (rule, batch,
    mesh, compaction) — the kernel_cache key both drivers share, so a
    warmup through one driver warms the other)."""
    import jax
    import jax.numpy as jnp

    rule_bs = min(ctx["batch_size"], _pow2(max(rp.total, 64)))
    if mesh is not None:
        from .parallel.mesh import pad_to_multiple

        rule_bs = pad_to_multiple(rule_bs, mesh.devices.size)
    pos_rule = pos_cache.get(rule_bs)
    if pos_rule is None:
        if mesh is not None:
            pos_rule = jax.device_put(
                np.arange(rule_bs, dtype=np.int32), ctx["shard"]
            )
        else:
            pos_rule = jnp.arange(rule_bs, dtype=jnp.int32)
        pos_cache[rule_bs] = pos_rule
    put = ctx["put"]
    order_dev = put(rp.order)
    units_dev = tuple(put(a) for a in (rp.ua, rp.la, rp.ub, rp.lb))
    kkey = (r, rule_bs, mesh_key(mesh), ctx["compact_dev"])
    fn = plan.kernel_cache.get(kkey)
    if fn is None:
        fn = plan.kernel_cache[kkey] = make_pair_emit_fn(
            rule_bs,
            n_prev=r,
            has_uid_mask=plan.uid_codes is not None,
            rank_filter=rp.rank_filter,
            own_res=rp.residual_fn,
            prev_res=tuple(p.residual_fn for p in plan.rules[:r]),
            mesh=mesh,
            compact=ctx["compact_dev"],
        )
    return rule_bs, pos_rule, order_dev, units_dev, fn


def iter_device_pairs(plan: DeviceBlockPlan, batch_size: int, mesh=None):
    """Drive the emission kernels over every rule, yielding
    ``(rule_index, i, j)`` host int32 chunks of at most ``batch_size``
    pairs in rule order (the same rule order the host sink sees).

    Chunk downloads run on a small thread pool ``_D2H_DEPTH`` deep (yield
    order stays submission order) so a chunk's D2H round trip overlaps the
    next chunk's kernel. Chunk shapes are power-of-two bucketed per rule —
    a steady-state emission loop compiles nothing after the first chunk of
    each rule.

    Telemetry: the driver accumulates host-side emission stats — chunks,
    pairs, pairs/sec, per-chunk budget fill and D2H thread-pool occupancy —
    and publishes ONE ambient ``blocking_device`` event when the stream
    ends (``python -m splink_tpu.obs summarize`` renders it). Pure host
    counters on the driver loop: the kernels and their jaxprs are
    untouched, and with no sink registered the publish is one falsy check.
    """
    import time as _time
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from .obs.events import publish

    if plan.n_candidates == 0:
        return
    ctx = _emission_context(plan, batch_size, mesh)
    batch_size = ctx["batch_size"]
    compact_dev = ctx["compact_dev"]
    ranks_dev = ctx["ranks"]
    codes_l_dev = ctx["codes_l"]
    codes_r_dev = ctx["codes_r"]
    uid_dev = ctx["uid"]
    res_ops_dev = ctx["res_ops"]
    pos_cache: dict = {}
    pool = ThreadPoolExecutor(max_workers=_D2H_DEPTH)
    inflight: deque = deque()
    # emission telemetry (host counters; published once in the finally).
    # fill/occupancy accumulate at SUBMIT time, so their means divide by
    # the submitted count — on an abandoned stream (the [abandoned] case
    # summarize flags) up to _D2H_DEPTH chunks are submitted but never
    # yielded, and dividing by the yield-time chunk count would inflate
    # exactly the diagnostics the event exists for
    stats = {"chunks": 0, "submitted": 0, "pairs": 0, "candidates": 0,
             "fill_sum": 0.0, "occ_sum": 0, "occ_max": 0,
             "completed": False}
    per_rule: dict[int, list] = {}
    t_start = _time.perf_counter()

    def account(res):
        r_idx, i, _j = res
        stats["chunks"] += 1
        stats["pairs"] += len(i)
        rr = per_rule.setdefault(r_idx, [0, 0])
        rr[0] += 1
        rr[1] += len(i)
        return res

    def own(arr, lanes):
        """Slice views into downloaded chunk buffers are zero-copy; when a
        slice keeps under half the buffer, copy so the consumer's sink
        doesn't pin the whole chunk buffer for a sliver of survivors."""
        return arr.copy() if 2 * len(arr) < lanes else arr

    def download(r, out_i, out_j, keep, n_valid):
        return (r, *_chunk_home(out_i, out_j, keep, n_valid, compact_dev,
                                mesh is not None, own))

    try:
        for r, rp in enumerate(plan.rules):
            if rp.total == 0:
                continue
            # rule batch clamped to the rule total (pow2 bucket): a
            # 38k-pair rule must not pad to a multi-M batch of dead lanes
            rule_bs, pos_rule, order_dev, units_dev, fn = _rule_emit_setup(
                plan, r, rp, ctx, mesh, pos_cache
            )
            for p0, p1, meta in _unit_batch_meta(rp.pc, rp.total, rule_bs):
                meta_dev = ctx["put"](meta)
                out_i, out_j, keep = fn(
                    pos_rule, order_dev, *units_dev, ranks_dev,
                    codes_l_dev, codes_r_dev, uid_dev, res_ops_dev,
                    meta_dev,
                )
                dispatched("block_pair_emit", out_i, positions=p1 - p0,
                           **ctx["on_mesh"])
                stats["submitted"] += 1
                stats["candidates"] += p1 - p0
                stats["fill_sum"] += (p1 - p0) / rule_bs
                inflight.append(
                    pool.submit(download, r, out_i, out_j, keep, p1 - p0)
                )
                occ = len(inflight)
                stats["occ_sum"] += occ
                if occ > stats["occ_max"]:
                    stats["occ_max"] = occ
                while len(inflight) > _D2H_DEPTH:
                    yield account(fetch_pooled(inflight.popleft()))
        while inflight:
            yield account(fetch_pooled(inflight.popleft()))
        stats["completed"] = True
    finally:
        # the consumer may abandon the generator mid-stream (a sink error):
        # do not leak pool threads or pinned buffers
        pool.shutdown(wait=False, cancel_futures=True)
        try:
            elapsed = max(_time.perf_counter() - t_start, 1e-9)
            n_sub = stats["submitted"] or 1
            publish(
                "blocking_device",
                rules=len(plan.rules),
                chunks=stats["chunks"],
                pairs=stats["pairs"],
                candidates=stats["candidates"],
                elapsed_s=round(elapsed, 4),
                pairs_per_sec=round(stats["pairs"] / elapsed),
                chunk_budget=batch_size,
                mean_chunk_fill=round(stats["fill_sum"] / n_sub, 4),
                d2h_occupancy_mean=round(stats["occ_sum"] / n_sub, 3),
                d2h_occupancy_max=stats["occ_max"],
                d2h_depth=_D2H_DEPTH,
                completed=stats["completed"],
                per_rule=[
                    {
                        "rule": plan.rules[r_idx].rule,
                        "chunks": c,
                        "pairs": p,
                    }
                    for r_idx, (c, p) in sorted(per_rule.items())
                ],
            )
        except Exception as e:  # noqa: BLE001 - telemetry must never break emission
            logger.debug("blocking_device telemetry publish failed: %s", e)


def device_block_rules(
    settings: dict,
    table: EncodedTable,
    n_left: int | None,
    sink,
    pair_consumer=None,
    mode: str = "auto",
    finish: bool = True,
):
    """The device tier of :func:`blocking.block_using_rules`: build the
    plan, stream chunked emission into the caller's sink, and return the
    finished PairIndex — or None to fall back to the host join (unsupported
    shape, or an "auto"-mode job too small to pay the jit warmup). A plan
    that FAILS to build raises, as an emission failure does: a compile
    error in the sort-join kernels must not hide behind the host join.
    ``finish=False`` leaves the sink open (and returns it unfinished) so
    the caller can append a further tier — the approximate LSH tier rides
    through this.
    """
    if mode == "auto":
        import jax

        from .blocking import estimate_pair_upper_bound

        if jax.default_backend() == "cpu":
            # CPU container, builders' round 8 (2 cores): the XLA-CPU tier
            # tied the numpy host join and trailed the native C++ one
            # ~0.75x — on the CPU backend auto keeps the host path; 'on'
            # still forces the device tier (tests, parity). That the
            # device join wins on the chip is unverified: no benchmark
            # cell runs it (PERF.md §7)
            return None
        # exact-rules-only bound: this gate weighs the EXACT tier's jit
        # warmup against its join size, so the approx tier's budget (which
        # runs its own kernels regardless) must not inflate the decision
        if estimate_pair_upper_bound(
            settings, table, n_left, include_approx=False
        ) < AUTO_MIN_PAIRS:
            return None
    plan = build_device_plan(settings, table, n_left)
    if plan is None:
        return None
    batch = int(
        settings.get("blocking_chunk_pairs") or DEFAULT_CHUNK_PAIRS
    )
    logger.info(
        "device blocking: %d candidate positions, %d rules",
        plan.n_candidates, len(plan.rules),
    )
    # onto whatever is open: the ``blocking`` stage, or the ``keyless_pairs``
    # span of blocking._block_every_pair (10,000 rows, no rule: 15)
    count_here(units=sum(len(rp.ua) for rp in plan.rules))
    for _r, i, j in iter_device_pairs(plan, batch):
        sink.append(i, j)
        if pair_consumer is not None:
            pair_consumer(
                i.astype(sink.idx_dtype, copy=False),
                j.astype(sink.idx_dtype, copy=False),
            )
    return sink.finish() if finish else sink


# --------------------------------------------------------------------------
# Sharded, out-of-core, resumable emission (the billion-row write path)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def make_chunk_digest_fn(mesh=None):
    """Jitted transfer-integrity digest over one emitted pair chunk:
    fn(i, j, keep) -> uint32 scalar, the wraparound sum of a per-lane
    multiplicative mix of (i, j) over the kept lanes.

    Computed ON DEVICE right after the emission kernel (the pairs are
    already resident), then re-derived on the host from the downloaded
    arrays (spill.chunk_digest_host) — a mismatch catches corruption in
    the D2H path itself, on top of disk rot (which the manifest's sha256
    covers). The sum
    is order-independent, which is exactly right: compaction reorders
    nothing but drops masked lanes, so the kept-lane multiset is the
    written multiset. Under a mesh the lane mixes are embarrassingly
    parallel along the sharded position axis and the sum lowers to one
    declared psum (shard_audit: spill_chunk_digest_sharded)."""
    import jax
    import jax.numpy as jnp

    from .spill import DIGEST_ADD, DIGEST_MUL

    jit_kwargs = {}
    if mesh is not None:
        from .parallel.mesh import replicated

        jit_kwargs = {"out_shardings": replicated(mesh)}

    @functools.partial(jax.jit, **jit_kwargs)
    def block_chunk_digest(i, j, keep):
        mixed = (i.astype(jnp.uint32) * jnp.uint32(DIGEST_MUL)) ^ (
            j.astype(jnp.uint32) + jnp.uint32(DIGEST_ADD)
        )
        mixed = mixed ^ (mixed >> jnp.uint32(15))
        return jnp.sum(
            jnp.where(keep, mixed, jnp.uint32(0)), dtype=jnp.uint32
        )

    return block_chunk_digest


@functools.lru_cache(maxsize=1)
def make_chunk_digest_compact_fn():
    """The transfer digest for COMPACTED emission chunks (the accelerator
    path, where on-device compaction halves D2H bytes): fn(i_ext, j, pos)
    -> uint32, with ``i_ext`` carrying the survivor count as its last lane
    (the emit kernel's compacted layout) and ``pos < count`` selecting
    exactly the survivor lanes. Same mix and sum as
    :func:`make_chunk_digest_fn`, so the host mirror over the downloaded
    prefix verifies it unchanged — without this twin, the accelerator
    backends would commit segments unverified."""
    import jax
    import jax.numpy as jnp

    from .spill import DIGEST_ADD, DIGEST_MUL

    @jax.jit
    def block_chunk_digest_compact(i_ext, j, pos):
        # static python index, NOT i_ext[-1]: a traced negative index
        # lowers through an int64 dynamic_slice under x64 (TA-DTYPE — the
        # same hazard the segment-sort kernel documents)
        cnt = i_ext[i_ext.shape[0] - 1]
        i = i_ext[:-1]
        keep = pos < cnt
        mixed = (i.astype(jnp.uint32) * jnp.uint32(DIGEST_MUL)) ^ (
            j.astype(jnp.uint32) + jnp.uint32(DIGEST_ADD)
        )
        mixed = mixed ^ (mixed >> jnp.uint32(15))
        return jnp.sum(
            jnp.where(keep, mixed, jnp.uint32(0)), dtype=jnp.uint32
        )

    return block_chunk_digest_compact


def _shard_unit_ranges(pc: np.ndarray, n_shards: int) -> list[tuple[int, int]]:
    """Partition a rule's units into ``n_shards`` contiguous [lo, hi) index
    ranges balanced by CUMULATIVE PAIR COUNT (not unit count — unit pair
    sizes vary by orders of magnitude, and a row-count split would leave
    one shard holding every monster rectangle). Contiguity is what makes a
    shard's position space a simple offset slice of the rule's pc table,
    so each shard drives the SAME emission kernel over its own
    batch-relative metadata."""
    n_units = len(pc) - 1
    total = int(pc[-1])
    if n_units <= 0 or total == 0:
        return [(0, 0)] * n_shards
    cuts = [
        int(np.searchsorted(pc, (total * k) // n_shards, side="left"))
        for k in range(n_shards + 1)
    ]
    cuts[0], cuts[-1] = 0, n_units
    # monotone repair: searchsorted on a heavily skewed pc can cross
    for k in range(1, n_shards + 1):
        cuts[k] = min(max(cuts[k], cuts[k - 1]), n_units)
    return [(cuts[k], cuts[k + 1]) for k in range(n_shards)]


def emit_pairs_sharded(
    plan: DeviceBlockPlan,
    store,
    batch_size: int,
    n_shards: int = 1,
    mesh=None,
    budget: int | None = None,
    fault_plan=None,
    shard_filter: tuple[int, int] | None = None,
):
    """Drive the sharded, resumable emission of ``plan`` into a
    :class:`~.spill.PairSpillStore`.

    Each rule's triangle/rectangle units partition into ``n_shards``
    contiguous pair-count-balanced ranges (:func:`_shard_unit_ranges`);
    every (rule, shard) streams fixed-shape pow2 chunks through the SAME
    emission kernels as :func:`iter_device_pairs` (one specialisation per
    rule — shard metadata rows are floored to the rule-wide kpad so a
    shard switch never recompiles), each chunk committing as one manifest
    segment. With ``mesh`` the chunk decode shards over the data axis via
    the collective-free ``block_pair_decode_sharded`` kernel and the host
    compacts per shard.

    Determinism is the resumability contract: segments enumerate in fixed
    (rule, shard, seq) order with deterministic contents, so a driver
    relaunched over a half-built store SKIPS the committed prefix (no
    kernel runs for it) and appends byte-identical segments from there —
    the approx tier's progressive-budget discipline applied globally:
    ``budget`` caps total emitted pairs across all rules and shards, the
    final segment truncating exactly at the envelope.

    ``shard_filter=(p, P)`` emits only shards with ``shard % P == p`` —
    the multi-controller partition: each host drives its own subset of
    every rule's shards into its own per-process store, and the spill-fed
    EM's cross-process stats reduction makes the union behave as one
    global pair set (the same contract as global_pair_slice over a
    materialised G). ``budget`` is enforced against THIS driver's
    committed store — i.e. PER PROCESS under a shard filter (each
    controller's envelope, not a cross-process global; a global cap wants
    ``budget // P`` per process).

    Returns a stats dict (segments, skipped, pairs, exhausted). The caller
    finalizes the store.
    """
    import time as _time
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from .obs.events import publish
    from .resilience import faults as _faults

    if fault_plan is None:
        fault_plan = _faults.active_plan()

    safe = (1 << 31) - 1 - plan.chunk * plan.chunk
    batch_size = min(max(int(batch_size), 64), safe)
    if mesh is not None:
        from .parallel.mesh import pad_to_multiple, pair_sharding, replicated

        msz = mesh.devices.size
        batch_size = pad_to_multiple(batch_size, msz)
        if batch_size > safe:
            batch_size = max(safe // msz, 1) * msz
        shard_s = pair_sharding(mesh)
        repl = replicated(mesh)
        put = lambda a: jax.device_put(jnp.asarray(a), repl)  # noqa: E731
        on_mesh = {"devices": msz}
    else:
        put = jnp.asarray
        on_mesh = {}

    compact_dev = mesh is None and jax.default_backend() != "cpu"
    ranks_dev = put(plan.ranks)
    codes_l_dev = put(
        plan.codes_l if len(plan.codes_l) else np.zeros((1, 1), np.int32)
    )
    codes_r_dev = put(
        plan.codes_r if len(plan.codes_r) else np.zeros((1, 1), np.int32)
    )
    uid_dev = put(
        plan.uid_codes if plan.uid_codes is not None
        else np.zeros(1, np.int32)
    )
    res_ops_dev = tuple(put(a) for a in plan.res_ops)
    digest_fn = (
        make_chunk_digest_compact_fn()
        if compact_dev
        else make_chunk_digest_fn(mesh)
    )
    pos_cache: dict = {}
    pool = ThreadPoolExecutor(max_workers=_D2H_DEPTH)
    inflight: deque = deque()
    stats = {"segments": 0, "skipped": 0, "pairs": 0, "exhausted": False}
    # resumed stores already carry pairs toward the budget envelope
    emitted = sum(s.pairs for s in store.segments)
    t_start = _time.perf_counter()

    def download(out_i, out_j, keep, n_valid, dig):
        """One chunk home as owning copies (segment bytes are written
        immediately, so zero-copy slices buy nothing) with its digest."""
        i, j = _chunk_home(out_i, out_j, keep, n_valid, compact_dev,
                           mesh is not None, lambda view, _lanes: view.copy())
        return i, j, None if dig is None else int(np.asarray(dig))

    def drain_one():
        nonlocal emitted
        r, s, k, fut = inflight.popleft()
        i, j, dig = fetch_pooled(fut)
        if budget is not None and emitted + len(i) > budget:
            keep = max(budget - emitted, 0)
            i, j, dig = i[:keep], j[:keep], None
            stats["exhausted"] = True
        emitted += len(i)
        stats["pairs"] += len(i)
        stats["segments"] += 1
        store.write_segment(
            r, s, k, i, j, digest=dig,
            fault_hook=lambda: fault_plan.fire(
                "emit_segment", rule=r, shard=s, seq=k
            ),
        )

    try:
        for r, rp in enumerate(plan.rules):
            if rp.total == 0:
                continue
            rule_bs = min(batch_size, _pow2(max(rp.total, 64)))
            if mesh is not None:
                rule_bs = pad_to_multiple(rule_bs, mesh.devices.size)
            ranges = _shard_unit_ranges(rp.pc, n_shards)
            # two-pass metadata build: learn each shard's natural kpad,
            # then floor every shard at the rule-wide max so all segments
            # of a rule share ONE kernel specialisation
            shard_metas: list[list] = []
            for lo, hi in ranges:
                if hi <= lo:
                    shard_metas.append([])
                    continue
                pc_rel = rp.pc[lo : hi + 1] - rp.pc[lo]
                shard_metas.append(
                    _unit_batch_meta(pc_rel, int(pc_rel[-1]), rule_bs)
                )
            kpad_rule = max(
                (m[0][2].shape[0] - 2 for m in shard_metas if m), default=0
            )
            for s_idx, (lo, hi) in enumerate(ranges):
                if shard_metas[s_idx] and (
                    shard_metas[s_idx][0][2].shape[0] - 2 < kpad_rule
                ):
                    pc_rel = rp.pc[lo : hi + 1] - rp.pc[lo]
                    shard_metas[s_idx] = _unit_batch_meta(
                        pc_rel, int(pc_rel[-1]), rule_bs, kpad_min=kpad_rule
                    )
            pos_rule = pos_cache.get(rule_bs)
            if pos_rule is None:
                if mesh is not None:
                    pos_rule = jax.device_put(
                        np.arange(rule_bs, dtype=np.int32), shard_s
                    )
                else:
                    pos_rule = jnp.arange(rule_bs, dtype=jnp.int32)
                pos_cache[rule_bs] = pos_rule
            order_dev = put(rp.order)
            units_dev = tuple(put(a) for a in (rp.ua, rp.la, rp.ub, rp.lb))
            kkey = (r, rule_bs, mesh_key(mesh), compact_dev)
            fn = plan.kernel_cache.get(kkey)
            if fn is None:
                fn = plan.kernel_cache[kkey] = make_pair_emit_fn(
                    rule_bs,
                    n_prev=r,
                    has_uid_mask=plan.uid_codes is not None,
                    rank_filter=rp.rank_filter,
                    own_res=rp.residual_fn,
                    prev_res=tuple(p.residual_fn for p in plan.rules[:r]),
                    mesh=mesh,
                    compact=compact_dev,
                )
            for s_idx, (lo, _hi) in enumerate(ranges):
                if shard_filter is not None and (
                    s_idx % shard_filter[1] != shard_filter[0]
                ):
                    continue
                for k, (_p0, p1, meta) in enumerate(shard_metas[s_idx]):
                    if store.segment_done(r, s_idx, k):
                        stats["skipped"] += 1
                        continue
                    if budget is not None:
                        # budget runs drain sequentially: the stop decision
                        # must depend only on COMMITTED pair counts, or a
                        # resumed run (which sees committed counts, not
                        # optimistic in-flight ones) would dispatch a
                        # different segment set than the uninterrupted one
                        while inflight:
                            drain_one()
                        if emitted >= budget:
                            stats["exhausted"] = True
                            raise StopIteration
                    meta = meta.copy()
                    meta[0] += lo  # shard units index the FULL unit tables
                    meta_dev = put(meta)
                    out_i, out_j, keep = fn(
                        pos_rule, order_dev, *units_dev, ranks_dev,
                        codes_l_dev, codes_r_dev, uid_dev, res_ops_dev,
                        meta_dev,
                    )
                    dispatched("block_pair_emit", out_i, positions=p1 - _p0,
                               **on_mesh)
                    dig = None
                    if keep is not None:
                        # compact layout passes positions (the count rides
                        # as out_i's last lane); uncompacted passes the
                        # keep mask directly
                        dig = (
                            digest_fn(out_i, out_j, pos_rule)
                            if compact_dev
                            else digest_fn(out_i, out_j, keep)
                        )
                    inflight.append(
                        (r, s_idx, k,
                         pool.submit(download, out_i, out_j, keep, p1 - _p0,
                                     dig))
                    )
                    while len(inflight) > _D2H_DEPTH:
                        drain_one()
        while inflight:
            drain_one()
    except StopIteration:
        while inflight:
            drain_one()
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        try:
            elapsed = max(_time.perf_counter() - t_start, 1e-9)
            publish(
                "blocking_spill",
                rules=len(plan.rules),
                shards=n_shards,
                segments=stats["segments"],
                skipped=stats["skipped"],
                pairs=stats["pairs"],
                pairs_per_sec=round(stats["pairs"] / elapsed),
                chunk_budget=batch_size,
                budget=budget,
                exhausted=stats["exhausted"],
                elapsed_s=round(elapsed, 4),
            )
        except Exception as e:  # noqa: BLE001 - telemetry must never break emission
            logger.debug("blocking_spill telemetry publish failed: %s", e)
    return stats


def spill_block_rules(
    settings: dict,
    table: EncodedTable,
    n_left: int | None,
    build_dir: str,
    budget: int | None = None,
):
    """The build_spill_dir write path: sharded resumable emission into
    ``<build_dir>/pairs``, returning a durable store-backed PairIndex — or
    None when the job's rule shapes need the host join (the caller falls
    back to the ordinary, non-resumable path with its own warning).

    A store already finalized for this exact job returns instantly (the
    idempotent-restart property a relaunch-loop harness needs); a
    half-built one resumes from its last committed segment.
    """
    import os

    from .parallel.mesh import mesh_from_settings
    from .resilience.checkpoint import settings_state_hash
    from .spill import PairSpillStore

    plan = build_device_plan(settings, table, n_left)
    if plan is None:
        return None
    from .blocking import _idx_dtype

    import jax

    from .parallel.distributed import distributed_is_initialized

    p_idx, p_cnt = 0, 1
    if distributed_is_initialized():
        p_idx, p_cnt = jax.process_index(), jax.process_count()
    mesh = mesh_from_settings(settings)
    n_shards = int(settings.get("emit_shard_chunks") or 0)
    if n_shards <= 0:
        n_shards = (mesh.devices.size if mesh is not None else 1) * p_cnt
    n_shards = max(n_shards, p_cnt)
    batch = int(settings.get("blocking_chunk_pairs") or DEFAULT_CHUNK_PAIRS)
    state_hash = settings_state_hash(
        settings, extra={"artifact": "pair_spill", "n_rows": int(table.n_rows)}
    )
    meta = {
        "state_hash": state_hash,
        "n_shards": n_shards,
        "chunk_pairs": batch,
        "budget": budget,
        "process_index": p_idx,
        "process_count": p_cnt,
        "rule_totals": [int(rp.total) for rp in plan.rules],
    }
    store = PairSpillStore.attach(
        os.path.join(build_dir, "pairs"), _idx_dtype(table.n_rows), meta
    )
    if store.completed:
        logger.info(
            "spill store at %s already finalized (%d pairs); reusing",
            store.directory, store.total_pairs,
        )
        return store.as_pair_index()
    with store:
        stats = emit_pairs_sharded(
            plan, store, batch, n_shards=n_shards, mesh=mesh,
            budget=budget,
            shard_filter=None if p_cnt == 1 else (p_idx, p_cnt),
        )
    store.finalize(exhausted=stats["exhausted"])
    logger.info(
        "spill emission: %d pairs in %d segments (%d resumed) at %s",
        store.total_pairs, len(store.segments), stats["skipped"],
        store.directory,
    )
    return store.as_pair_index()


# --------------------------------------------------------------------------
# Serving bucket CSR (serve/index.py)
# --------------------------------------------------------------------------


def build_bucket_csr(codes: np.ndarray):
    """Device bucket-CSR build over one rule's key codes for the serving
    index: (rows_sorted, starts, sizes, row_bucket) int32 arrays bit-equal
    to the host ``_sort_groups`` + scatter construction, or None when the
    codes don't fit the device lanes (the caller falls back to the host
    build)."""
    n = len(codes)
    if n == 0 or int(codes.max(initial=0)) >= _IMAX:
        return None
    m = _pow2(n)
    padded = codes.astype(np.int32)
    if m != n:
        padded = np.concatenate([padded, np.full(m - n, -1, np.int32)])
    fn = make_bucket_csr_fn()
    row_s, starts, sizes, row_bucket, n_seg, n_valid = fn(padded)
    n_valid_h = int(np.asarray(n_valid))
    n_seg_h = int(np.asarray(n_seg))
    starts = np.asarray(starts)[:n_seg_h]
    sizes = np.asarray(sizes)[:n_seg_h]
    live = starts < n_valid_h  # drop the trailing null/pad segment
    return (
        np.asarray(row_s)[:n_valid_h],
        starts[live],
        sizes[live],
        np.asarray(row_bucket)[:n],
    )
