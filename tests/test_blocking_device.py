"""Device-native blocking (splink_tpu/blocking_device.py).

The host join in blocking.py is the parity ORACLE: on every supported rule
shape the device tier's pair set must be bit-equal AS A SET — across
exact/multi-column/sequential rules, null keys, asymmetric keys (dedupe
name-swap and link tables), duplicate uids, residual predicates, uneven
chunk boundaries and budget-capped runs. Plus: the serving bucket CSR from
the device kernel is bit-equal to the host construction, steady-state
emission never recompiles, int32 pair indices hold on both tiers (spill
included), and the new audit registrations are falsifiable (a broken twin
trips TA-DTYPE / SA-COLL).
"""

import warnings

import numpy as np
import pandas as pd
import pytest

from splink_tpu.blocking import block_using_rules
from splink_tpu.blocking_device import (
    build_bucket_csr,
    build_device_plan,
    iter_device_pairs,
)
from splink_tpu.data import concat_tables, encode_table
from splink_tpu.settings import complete_settings_dict


def _settings(rules, link_type="dedupe_only", **extra):
    s = {
        "link_type": link_type,
        "comparison_columns": [
            {"col_name": "first_name"},
            {"col_name": "surname"},
            {"col_name": "amount", "data_type": "numeric"},
        ],
        "blocking_rules": list(rules),
    }
    s.update(extra)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return complete_settings_dict(s)


# names deliberately OVERLAP across first_name/surname so asymmetric
# (name-swap) joins produce pairs
_NAMES = ["john", "mary", "jones", "smith", None, "lee", "ann"]


def _df(n, seed, dup_uids=False):
    r = np.random.default_rng(seed)
    uid = np.arange(n) // 2 if dup_uids else np.arange(n)
    return pd.DataFrame(
        {
            "unique_id": uid,
            "first_name": r.choice(_NAMES, n),
            "surname": r.choice(_NAMES, n),
            "amount": r.choice([1.0, 2.5, 3.0, np.nan], n),
        }
    )


def _block_both(settings, table, n_left=None, chunk=None):
    """(host_pairs, device_pairs) as sets; asserts the device tier actually
    ran (plan not rejected) unless the caller expects fallback."""
    sh = dict(settings)
    sh["device_blocking"] = "off"
    sd = dict(settings)
    sd["device_blocking"] = "on"
    if chunk:
        sd["blocking_chunk_pairs"] = chunk
    ph = block_using_rules(sh, table, n_left)
    pdv = block_using_rules(sd, table, n_left)
    host = set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))
    dev = set(zip(pdv.idx_l.tolist(), pdv.idx_r.tolist()))
    return host, dev, ph, pdv


DEDUPE_RULESETS = [
    ["l.first_name = r.first_name"],
    ["l.first_name = r.first_name and l.surname = r.surname"],
    # sequential rules: rule 2 excludes every rule-1 pair (null-safe NOT)
    ["l.first_name = r.first_name", "l.surname = r.surname"],
    # asymmetric name-swap key over one table
    ["l.first_name = r.surname"],
    # asym + symmetric key in one rule, after a plain rule
    ["l.surname = r.surname", "l.first_name = r.surname and l.amount = r.amount"],
    # derived-key expression
    ["substr(l.surname,1,2) = substr(r.surname,1,2)"],
    # residual predicates (compiled to device masks)
    ["l.first_name = r.first_name and l.amount + 1 > r.amount"],
    ["l.surname = r.surname and l.amount <= r.amount", "l.first_name = r.first_name"],
]


@pytest.mark.parametrize("chunk", [None, 7])
@pytest.mark.parametrize("rules", DEDUPE_RULESETS)
def test_device_parity_dedupe(rules, chunk):
    s = _settings(rules)
    t = encode_table(_df(120, 3), s)
    assert build_device_plan(s, t) is not None, "plan unexpectedly rejected"
    host, dev, _, _ = _block_both(s, t, chunk=chunk)
    assert dev == host
    assert host, f"degenerate fixture: no pairs for {rules}"


@pytest.mark.parametrize("chunk", [None, 13])
@pytest.mark.parametrize(
    "rules",
    [
        ["l.first_name = r.first_name"],
        ["l.first_name = r.surname"],  # asymmetric link key
        ["l.first_name = r.first_name", "l.surname = r.surname"],
    ],
)
def test_device_parity_link_only(rules, chunk):
    s = _settings(rules, link_type="link_only")
    t = concat_tables(_df(70, 5), _df(90, 6), s)
    host, dev, _, _ = _block_both(s, t, n_left=70, chunk=chunk)
    assert dev == host
    assert host


@pytest.mark.parametrize(
    "rules",
    [
        ["l.first_name = r.first_name", "l.surname = r.surname"],
        ["l.first_name = r.surname"],
    ],
)
def test_device_parity_link_and_dedupe(rules):
    s = _settings(rules, link_type="link_and_dedupe")
    t = concat_tables(_df(60, 7), _df(50, 8), s)
    host, dev, _, _ = _block_both(s, t, n_left=60, chunk=11)
    assert dev == host
    assert host


@pytest.mark.parametrize("link_type", ["dedupe_only", "link_and_dedupe"])
def test_device_parity_duplicate_uids(link_type):
    """Duplicate ordering keys: the strict l.key < r.key ordering drops
    equal-key pairs — the device uid mask must reproduce it exactly."""
    rules = ["l.first_name = r.first_name", "l.first_name = r.surname"]
    s = _settings(rules, link_type=link_type)
    if link_type == "dedupe_only":
        t = encode_table(_df(100, 9, dup_uids=True), s)
        n_left = None
    else:
        t = concat_tables(
            _df(50, 10, dup_uids=True), _df(60, 11, dup_uids=True), s
        )
        n_left = 50
    host, dev, _, _ = _block_both(s, t, n_left=n_left, chunk=17)
    assert dev == host
    assert host


def test_device_parity_null_only_rule():
    """A rule whose key is null on every row joins nothing, on both tiers."""
    s = _settings(["l.first_name = r.first_name"])
    df = _df(30, 12)
    df["first_name"] = None
    t = encode_table(df, s)
    host, dev, _, _ = _block_both(s, t)
    assert host == dev == set()


def test_budget_capped_run_parity_and_chunk_shapes():
    """An explicit pair budget streams fixed-shape chunks: every emitted
    chunk respects the cap, uneven tails included, and the union equals
    the host set."""
    s = _settings(
        ["l.first_name = r.first_name", "l.surname = r.surname"],
        device_blocking="on",
    )
    t = encode_table(_df(300, 13), s)
    plan = build_device_plan(s, t)
    assert plan is not None and plan.n_candidates > 64
    budget = 64
    chunks = list(iter_device_pairs(plan, budget))
    assert chunks
    for _r, i, j in chunks:
        assert len(i) == len(j) <= budget
    got = {
        (int(a), int(b)) for _r, i, j in chunks for a, b in zip(i, j)
    }
    sh = dict(s)
    sh["device_blocking"] = "off"
    ph = block_using_rules(sh, t)
    assert got == set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))


def test_host_chunk_iterators_bound_monster_groups():
    """The per-chunk cap holds for ANY group shape: a single a-row (or an
    r-side) wider than the cap splits its contiguous range, so no chunk —
    and no expansion intermediate — ever exceeds ~cap pairs."""
    from splink_tpu.blocking import (
        _cross_join,
        _iter_cross_join_chunks,
        _iter_self_join_chunks,
        _self_join,
    )

    cap = 50
    codes = np.zeros(200, np.int64)  # ONE giant group: 19900 pairs
    chunks = list(_iter_self_join_chunks(codes, None, cap))
    assert len(chunks) > 1
    assert all(len(i) <= cap for i, _ in chunks)
    got = {(a, b) for i, j in chunks for a, b in zip(i.tolist(), j.tolist())}
    fi, fj = _self_join(codes)
    assert got == set(zip(fi.tolist(), fj.tolist()))

    codes = np.zeros(203, np.int64)
    left = np.arange(3, dtype=np.int64)
    right = np.arange(3, 203, dtype=np.int64)  # r-side 200 >> cap
    chunks = list(_iter_cross_join_chunks(codes, left, right, None, cap))
    assert all(len(i) <= cap for i, _ in chunks)
    got = {(a, b) for i, j in chunks for a, b in zip(i.tolist(), j.tolist())}
    fi, fj = _cross_join(codes, left, right)
    assert got == set(zip(fi.tolist(), fj.tolist()))


def test_mesh_emission_parity():
    """The sharded emission driver (positions sharded over the virtual
    8-device mesh, host compacting per shard) yields the same pair set as
    the host oracle."""
    from splink_tpu.parallel.mesh import make_mesh

    s = _settings(
        ["l.first_name = r.first_name", "l.surname = r.surname"],
    )
    t = encode_table(_df(150, 23), s)
    plan = build_device_plan(s, t)
    assert plan is not None
    mesh = make_mesh(8)
    got = {
        (int(a), int(b))
        for _r, i, j in iter_device_pairs(plan, 256, mesh=mesh)
        for a, b in zip(i, j)
    }
    sh = dict(s)
    sh["device_blocking"] = "off"
    ph = block_using_rules(sh, t)
    assert got == set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))


def test_zero_steady_state_recompiles():
    """After the first emission warms the per-rule kernels, re-driving the
    SAME plan — uneven tail chunks and all — compiles nothing."""
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor

    install_compile_monitor()
    s = _settings(["l.first_name = r.first_name", "l.surname = r.surname"])
    t = encode_table(_df(250, 14), s)
    plan = build_device_plan(s, t)
    assert plan is not None
    first = [c for c in iter_device_pairs(plan, 128)]
    c0 = compile_requests()
    second = [c for c in iter_device_pairs(plan, 128)]
    c1 = compile_requests()
    assert c1 == c0, f"{c1 - c0} steady-state recompiles"
    flat = lambda cs: [(r, i.tolist(), j.tolist()) for r, i, j in cs]  # noqa: E731
    assert flat(first) == flat(second)


def test_pair_index_int32_both_tiers(tmp_path):
    """Satellite: PairIndex emits int32 indices when n_rows < 2^31 on BOTH
    tiers, spill path included (the memmap inherits the narrow dtype, so
    spill files halve too)."""
    s = _settings(["l.first_name = r.first_name"])
    t = encode_table(_df(80, 15), s)
    for mode in ("off", "on"):
        cfg = dict(s)
        cfg["device_blocking"] = mode
        pairs = block_using_rules(cfg, t)
        assert pairs.idx_l.dtype == np.int32, mode
        assert pairs.idx_r.dtype == np.int32, mode
        cfg_spill = dict(cfg)
        cfg_spill["spill_dir"] = str(tmp_path / f"spill_{mode}")
        spilled = block_using_rules(cfg_spill, t)
        assert spilled.idx_l.dtype == np.int32, mode
        assert spilled.spill_tmp is not None
        assert set(zip(spilled.idx_l.tolist(), spilled.idx_r.tolist())) == set(
            zip(pairs.idx_l.tolist(), pairs.idx_r.tolist())
        )


def test_host_chunked_emission_matches_unchunked():
    """Satellite: the host join consumes per-chunk expansion intermediates
    under blocking_chunk_pairs — the emitted pair index is bit-identical
    to the unchunked run (same enumeration order, not just same set)."""
    s = _settings(
        ["l.first_name = r.first_name", "l.first_name = r.surname"],
        device_blocking="off",
    )
    t = encode_table(_df(150, 16), s)
    base = block_using_rules(s, t)
    for cap in (5, 64, 1001):
        cfg = dict(s)
        cfg["blocking_chunk_pairs"] = cap
        got = block_using_rules(cfg, t)
        assert np.array_equal(got.idx_l, base.idx_l), cap
        assert np.array_equal(got.idx_r, base.idx_r), cap


def test_pair_consumer_chunks_cover_stream():
    """The overlap consumer sees every device chunk, in order, with the
    sink's dtype."""
    s = _settings(["l.first_name = r.first_name"], device_blocking="on",
                  blocking_chunk_pairs=64)
    t = encode_table(_df(200, 17), s)
    seen = []
    pairs = block_using_rules(
        s, t, pair_consumer=lambda i, j: seen.append((i.copy(), j.copy()))
    )
    assert seen and all(i.dtype == np.int32 for i, _ in seen)
    got_l = np.concatenate([i for i, _ in seen])
    got_r = np.concatenate([j for _, j in seen])
    assert np.array_equal(got_l, pairs.idx_l)
    assert np.array_equal(got_r, pairs.idx_r)


def test_unsupported_shapes_fall_back():
    """A keyless rule beside a residual and monster groups reject the device
    plan; the host path serves them (block_using_rules still answers). No
    rule at all is NOT among them: one keyless group
    (tests/test_cartesian_deployment.py has its parity)."""
    # a rule with no equality condition anywhere in the list
    s = _settings(["l.amount < r.amount"])
    t = encode_table(_df(25, 18), s)
    assert build_device_plan(s, t) is None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        host, dev, _, _ = _block_both(s, t)
    assert dev == host
    s = _settings([])
    plan = build_device_plan(s, t)
    assert [len(r.ua) for r in plan.rules] == [1] and plan.n_candidates == 300
    host, dev, _, _ = _block_both(s, t, chunk=64)
    assert dev == host and len(host) == 300


def test_monster_group_falls_back(monkeypatch):
    import splink_tpu.pairgen as pairgen

    monkeypatch.setattr(pairgen, "MAX_UNITS_PER_GROUP", 2)
    s = _settings(["l.first_name = r.first_name"])
    df = _df(120, 19)
    df["first_name"] = "same"  # one giant group
    t = encode_table(df, s)
    assert build_device_plan(s, t, chunk=4) is None


def test_auto_gate_uses_host_below_threshold(monkeypatch):
    """mode='auto' must not pay the jit warmup on a job whose estimated
    pair bound is tiny — device_block_rules returns None untouched."""
    from splink_tpu import blocking_device

    s = _settings(["l.first_name = r.first_name"])
    t = encode_table(_df(40, 20), s)

    def boom(*a, **k):  # the plan build must never run
        raise AssertionError("plan built for a tiny auto-mode job")

    monkeypatch.setattr(blocking_device, "build_device_plan", boom)
    assert (
        blocking_device.device_block_rules(s, t, None, None, None, "auto")
        is None
    )


# ----------------------------------------------------------------------
# Sharded spill emission (the billion-row write path)
# ----------------------------------------------------------------------


def _emit_to_store(plan, tmp_path, name, n_shards, batch=128, mesh=None):
    from splink_tpu.spill import PairSpillStore

    store = PairSpillStore.attach(str(tmp_path / name), np.int32, {})
    from splink_tpu.blocking_device import emit_pairs_sharded

    with store:
        emit_pairs_sharded(plan, store, batch, n_shards=n_shards, mesh=mesh)
    store.finalize()
    pi = store.as_pair_index()
    return set(zip(pi.idx_l.tolist(), pi.idx_r.tolist()))


@pytest.mark.parametrize("rules", DEDUPE_RULESETS)
def test_sharded_emission_pair_set_parity(rules, tmp_path):
    """ACCEPTANCE: the sharded spill emission's pair set exactly equals
    the single-shard device tier's (and the host oracle's) on every rule
    shape — shards partition units, never pairs."""
    s = _settings(rules)
    t = encode_table(_df(120, 3), s)
    plan = build_device_plan(s, t)
    assert plan is not None
    single = {
        (int(a), int(b))
        for _r, i, j in iter_device_pairs(plan, 128)
        for a, b in zip(i, j)
    }
    sharded = _emit_to_store(plan, tmp_path, "sharded", n_shards=3)
    assert sharded == single
    sh = dict(s)
    sh["device_blocking"] = "off"
    ph = block_using_rules(sh, t)
    assert sharded == set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))
    assert sharded, f"degenerate fixture: no pairs for {rules}"


@pytest.mark.parametrize(
    "rules",
    [
        ["l.first_name = r.first_name"],
        ["l.first_name = r.surname"],
        ["l.first_name = r.first_name", "l.surname = r.surname"],
    ],
)
def test_sharded_emission_parity_link_only(rules, tmp_path):
    s = _settings(rules, link_type="link_only")
    t = concat_tables(_df(70, 5), _df(90, 6), s)
    plan = build_device_plan(s, t, n_left=70)
    assert plan is not None
    sharded = _emit_to_store(plan, tmp_path, "link", n_shards=4, batch=64)
    sh = dict(s)
    sh["device_blocking"] = "off"
    ph = block_using_rules(sh, t, 70)
    assert sharded == set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))
    assert sharded


def test_sharded_emission_mesh_parity(tmp_path):
    """Shard scheduling composes with the mesh decode: units partition
    across shards AND each chunk's positions shard over the virtual
    8-device mesh (block_pair_decode_sharded)."""
    from splink_tpu.parallel.mesh import make_mesh

    s = _settings(
        ["l.first_name = r.first_name", "l.surname = r.surname"]
    )
    t = encode_table(_df(150, 23), s)
    plan = build_device_plan(s, t)
    sharded = _emit_to_store(
        plan, tmp_path, "mesh", n_shards=4, batch=256, mesh=make_mesh(8)
    )
    sh = dict(s)
    sh["device_blocking"] = "off"
    ph = block_using_rules(sh, t)
    assert sharded == set(zip(ph.idx_l.tolist(), ph.idx_r.tolist()))


def test_sharded_emission_zero_steady_state_recompiles(tmp_path):
    """ACCEPTANCE: across chunk shapes, shard switches AND spill segments,
    a second drive of the same plan compiles nothing — shard metadata
    rows are floored to the rule-wide kpad so every (rule, shard, seq)
    shares one specialisation."""
    from splink_tpu.blocking_device import emit_pairs_sharded
    from splink_tpu.obs.metrics import (
        compile_requests,
        install_compile_monitor,
    )
    from splink_tpu.spill import PairSpillStore

    install_compile_monitor()
    s = _settings(["l.first_name = r.first_name", "l.surname = r.surname"])
    t = encode_table(_df(250, 14), s)
    plan = build_device_plan(s, t)
    store1 = PairSpillStore.attach(str(tmp_path / "one"), np.int32, {})
    with store1:
        emit_pairs_sharded(plan, store1, 128, n_shards=3)
    store1.finalize()
    c0 = compile_requests()
    store2 = PairSpillStore.attach(str(tmp_path / "two"), np.int32, {})
    with store2:
        emit_pairs_sharded(plan, store2, 128, n_shards=3)
    store2.finalize()
    c1 = compile_requests()
    assert c1 == c0, f"{c1 - c0} steady-state recompiles across segments"
    a = store1.as_pair_index()
    b = store2.as_pair_index()
    assert np.array_equal(a.idx_l, b.idx_l)
    assert np.array_equal(a.idx_r, b.idx_r)


def test_spill_block_rules_settings_shapes(tmp_path):
    """emit_shard_chunks resolves the shard count; the host-only rule
    shapes fall back (None) instead of half-building a store."""
    from splink_tpu.blocking_device import spill_block_rules

    s = _settings(
        ["l.first_name = r.first_name"], emit_shard_chunks=2,
        blocking_chunk_pairs=256,
    )
    t = encode_table(_df(120, 19), s)
    pi = spill_block_rules(s, t, None, str(tmp_path / "ok"))
    assert pi is not None
    import json as _json
    import os as _os

    m = _json.load(
        open(_os.path.join(str(tmp_path / "ok"), "pairs", "pair_manifest.json"))
    )
    assert m["meta"]["n_shards"] == 2
    assert {seg["shard"] for seg in m["segments"]} <= {0, 1}
    # a keyless rule WITH a residual: no device plan, caller falls back
    s2 = _settings(["l.amount < r.amount"])
    t2 = encode_table(
        _df(25, 18).assign(amount=np.arange(25.0)), s2
    )
    assert spill_block_rules(s2, t2, None, str(tmp_path / "no")) is None
    # NO rule at all: one keyless group of every row in the same plan, so
    # the spill store holds every pair once, as the host oracle orders them
    s3 = _settings([], emit_shard_chunks=2, blocking_chunk_pairs=256)
    t3 = encode_table(_df(60, 20), s3)
    pi3 = spill_block_rules(s3, t3, None, str(tmp_path / "all"))
    assert pi3 is not None and pi3.n_pairs == 60 * 59 // 2
    from splink_tpu.blocking import cartesian_block

    want = cartesian_block(s3, t3)
    assert set(zip(pi3.idx_l.tolist(), pi3.idx_r.tolist())) == set(
        zip(want.idx_l.tolist(), want.idx_r.tolist())
    )


# ----------------------------------------------------------------------
# Serving bucket CSR
# ----------------------------------------------------------------------


def test_bucket_csr_matches_host_construction():
    from splink_tpu.blocking import _key_codes, _sort_groups

    s = _settings(["l.first_name = r.first_name"])
    t = encode_table(_df(173, 21), s)  # non-power-of-two row count
    codes = _key_codes(t, ["first_name"])
    csr = build_bucket_csr(codes)
    assert csr is not None
    rows_sorted, starts, sizes, row_bucket = csr
    rows = np.flatnonzero(codes >= 0).astype(np.int32)
    h_rows, _, h_starts, h_sizes = _sort_groups(codes, rows)
    assert np.array_equal(rows_sorted, h_rows)
    assert np.array_equal(starts, h_starts.astype(np.int32))
    assert np.array_equal(sizes, h_sizes.astype(np.int32))
    h_bucket = np.full(t.n_rows, -1, np.int32)
    h_bucket[h_rows] = np.repeat(
        np.arange(len(h_sizes), dtype=np.int32), h_sizes
    )
    assert np.array_equal(row_bucket, h_bucket)


def test_serve_rule_device_and_host_builds_agree():
    from splink_tpu.serve.index import _build_serve_rule

    s = _settings(["l.first_name = r.first_name"])
    t = encode_table(_df(140, 22), s)
    dev = _build_serve_rule(t, "l.first_name = r.first_name", device=True)
    host = _build_serve_rule(t, "l.first_name = r.first_name", device=False)
    assert np.array_equal(dev.rows_sorted, host.rows_sorted)
    assert np.array_equal(dev.starts, host.starts)
    assert np.array_equal(dev.sizes, host.sizes)
    assert np.array_equal(dev.row_bucket, host.row_bucket)
    assert dev.bucket_of == host.bucket_of


# ----------------------------------------------------------------------
# Settings keys
# ----------------------------------------------------------------------


def test_blocking_settings_keys_complete_and_validate():
    from splink_tpu.validate import ValidationError, validate_settings

    s = _settings(["l.first_name = r.first_name"])
    assert s["device_blocking"] == "auto"
    assert s["blocking_chunk_pairs"] == 4194304
    for bad in (
        {"device_blocking": "sometimes"},
        {"device_blocking": 1},
        {"blocking_chunk_pairs": 0},
        {"blocking_chunk_pairs": "big"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_settings(["l.first_name = r.first_name"], **bad))
    validate_settings(
        _settings(
            ["l.first_name = r.first_name"],
            device_blocking="on",
            blocking_chunk_pairs=1024,
        )
    )


# ----------------------------------------------------------------------
# Audit registrations: clean AND falsifiable
# ----------------------------------------------------------------------


def test_blocking_kernels_registered_and_clean():
    from splink_tpu.analysis.trace_audit import run_audit

    findings, audited = run_audit(
        ["block_segment_sort", "block_bucket_csr", "block_pair_emit"]
    )
    assert audited == 3
    assert not findings, "\n".join(f.format() for f in findings)


def test_blocking_shard_kernel_registered_and_clean():
    from splink_tpu.analysis.shard_audit import run_shard_audit

    findings, audited = run_shard_audit(["block_pair_decode_sharded"])
    assert audited == 1
    assert not findings, "\n".join(f.format() for f in findings)


def test_bad_emit_twin_trips_ta_dtype():
    """An unpinned arange in the emission compaction goes int64 under the
    forced-x64 trace — the dtype leak TA-DTYPE exists to catch."""
    from splink_tpu.analysis.trace_audit import KernelSpec, audit_kernel

    def build():
        import jax.numpy as jnp

        def bad(keep, i):
            slots = jnp.arange(keep.shape[0])  # unpinned: int64 under x64
            kcum = jnp.cumsum(keep.astype(jnp.int32), dtype=jnp.int32)
            dest = jnp.where(keep, kcum - 1, keep.shape[0])
            return jnp.zeros(keep.shape[0], jnp.int32).at[dest].set(
                i + slots.astype(jnp.int32) * 0, mode="drop"
            )

        keep = jnp.zeros(16, bool)
        i = jnp.zeros(16, jnp.int32)
        return bad, (keep, i), {}

    findings = audit_kernel(KernelSpec(name="bad_block_emit_dtype", build=build))
    assert any(f.rule == "TA-DTYPE" for f in findings), [
        f.format() for f in findings
    ]


def test_spill_digest_kernels_registered_and_clean():
    from splink_tpu.analysis.shard_audit import run_shard_audit
    from splink_tpu.analysis.trace_audit import run_audit

    findings, audited = run_audit(
        ["spill_chunk_digest", "spill_chunk_digest_compact"]
    )
    assert audited == 2
    assert not findings, "\n".join(f.format() for f in findings)
    findings, audited = run_shard_audit(["spill_chunk_digest_sharded"])
    assert audited == 1
    assert not findings, "\n".join(f.format() for f in findings)


def test_bad_digest_shard_twin_trips_sa_coll():
    """FALSIFIABILITY (acceptance): the digest's cross-shard sum is its
    ONE declared collective — a twin registered WITHOUT the declaration
    must trip SA-COLL, proving the audit would catch a kernel that grew
    undeclared cross-device traffic."""
    from splink_tpu.analysis.shard_audit import (
        register_shard_kernel,
        run_shard_audit,
    )

    registry: dict = {}

    @register_shard_kernel(
        "bad_spill_digest_sharded", n_pairs=64, registry=registry
    )  # no allow_collectives: the psum is undeclared
    def _build():
        import jax

        from splink_tpu.analysis.shard_audit import audit_mesh
        from splink_tpu.blocking_device import make_chunk_digest_fn
        from splink_tpu.parallel.mesh import pair_sharding

        mesh = audit_mesh()
        fn = make_chunk_digest_fn(mesh)
        shard = pair_sharding(mesh)
        i = jax.device_put(np.zeros(64, np.int32), shard)
        j = jax.device_put(np.zeros(64, np.int32), shard)
        keep = jax.device_put(np.ones(64, bool), shard)
        return fn, (i, j, keep), {}

    findings, audited = run_shard_audit(registry=registry, baselines={})
    assert audited == 1
    assert any(f.rule == "SA-COLL" for f in findings), [
        f.format() for f in findings
    ]


def test_doctored_digest_mem_baseline_trips_pa_mem():
    """FALSIFIABILITY (acceptance): a perf baseline claiming the digest
    executable used to move fewer bytes makes PA-MEM fire — the measured
    layer would catch a memory regression in the new kernel."""
    import copy

    from splink_tpu.analysis import perf_audit as pa

    kernels = {}
    for cell in pa.perf_plan(["spill_chunk_digest"]):
        kernels.setdefault(cell.kernel, {})[cell.label] = pa.measure_cell(
            cell, best_of=2
        )
    base = {"tiers": {pa.current_tier(): {"kernels": kernels}}}
    doctored = copy.deepcopy(base)
    cell0 = doctored["tiers"][pa.current_tier()]["kernels"][
        "spill_chunk_digest"
    ]
    label = next(iter(cell0))
    cell0[label]["argument_bytes"] = cell0[label]["argument_bytes"] / 10.0
    findings, _ = pa.run_perf_audit(
        ["spill_chunk_digest"], doctored, best_of=2, remeasure=2
    )
    mem = [f for f in findings if f.rule == "PA-MEM"]
    assert mem and "argument_bytes" in mem[0].message
    # the honest measurement stays clean
    findings, _ = pa.run_perf_audit(
        ["spill_chunk_digest"], base, best_of=2, remeasure=2
    )
    assert not [f for f in findings if f.rule == "PA-MEM"]


def test_bad_shard_twin_trips_sa_coll():
    """Sorting INSIDE the sharded decode — the unpartitionable op the
    design keeps out of the mesh kernel — forces GSPMD to gather the
    sharded position axis: SA-COLL fires."""
    from splink_tpu.analysis.shard_audit import (
        register_shard_kernel,
        run_shard_audit,
    )

    registry: dict = {}

    @register_shard_kernel(
        "bad_block_sort_sharded", n_pairs=64, registry=registry
    )
    def _build():
        import jax

        from splink_tpu.analysis.shard_audit import audit_mesh
        from splink_tpu.parallel.mesh import pair_sharding

        mesh = audit_mesh()
        codes = jax.device_put(
            np.zeros(64, np.int32), pair_sharding(mesh)
        )

        def bad(codes):
            return jax.lax.sort((codes,), num_keys=1)[0]

        return bad, (codes,), {}

    findings, audited = run_shard_audit(registry=registry, baselines={})
    assert audited == 1
    assert any(f.rule == "SA-COLL" for f in findings), [
        f.format() for f in findings
    ]
