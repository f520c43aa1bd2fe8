"""Every virtual pair is decoded once (ROADMAP A3): the pattern kernel hands
back the row pairs it computed its ids from, they travel home with the ids,
and the score stream takes them. Held here: the kernel's pairs equal the host
oracle's (``pairgen.decode_positions``) position for position, for every kind
of plan, with and without a mesh, and the one-chip and the mesh kernels give
the same ids and histogram; the scored frame equals one assembled from the oracle's pairs, row order
included, in the stored and the recompute stream; and a pass nobody wants ids
from downloads nothing per pair.
"""

import warnings

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.data import concat_tables, encode_table
from splink_tpu.gammas import GammaProgram
from splink_tpu.linker import _FrameWriter
from splink_tpu.pairgen import (
    _virtual_pass_iter,
    build_virtual_plan,
    compute_virtual_pattern_ids,
    decode_positions,
)
from splink_tpu.parallel.mesh import make_mesh
from splink_tpu.settings import complete_settings_dict
from splink_tpu.utils.profiling import StageTimer, spans


def _settings(rules, link_type="dedupe_only", cols=None, **over):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return complete_settings_dict(
            {
                "link_type": link_type,
                "comparison_columns": cols
                or [{"col_name": "name", "num_levels": 2}],
                "blocking_rules": rules,
                **over,
            }
        )


def _people(n, seed):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", None], n),
            "surname": rng.choice(
                ["smithson", "smithers", "smyth", "jones", None], n
            ),
            "city": rng.choice([f"c{k}" for k in range(max(n // 30, 2))], n),
            "dob": rng.choice([f"d{k}" for k in range(max(n // 8, 2))], n),
            "age": rng.integers(20, 60, n).astype(float),
        }
    )


def _self_join(rules, chunk, seed=13, n=300):
    s = _settings(rules)
    table = encode_table(_people(n, seed), s)
    return s, table, build_virtual_plan(s, table, chunk=chunk)


def _two_frames(link_type, chunk, rules=("l.city = r.city", "l.dob = r.dob")):
    df = _people(200, seed=29)
    df_l, df_r = df.iloc[:110].copy(), df.iloc[110:].copy()
    # overlapping uid spaces, and a uid held twice on the left: the uid mask
    # of link_and_dedupe has pairs to drop
    df_r = df_r.assign(unique_id=df_r["unique_id"] - 90)
    df_l.iloc[3, df_l.columns.get_loc("unique_id")] = df_l.iloc[2]["unique_id"]
    s = _settings(list(rules), link_type=link_type)
    table = concat_tables(df_l, df_r, s)
    return s, table, build_virtual_plan(s, table, n_left=len(df_l), chunk=chunk)


# every kind of plan the virtual pair index builds
# (tests/test_pairgen.py, tests/test_derived_keys.py): name -> (settings, table, plan)
PLANS = {
    # multi-chunk groups: triangle units on the diagonal, rectangle units off
    # it, a later rule masked by the earlier ones
    "self_join_triangles_and_rectangles": lambda: _self_join(
        ["l.dob = r.dob", "l.city = r.city", "l.name = r.name"], chunk=8
    ),
    "self_join_one_unit_a_group": lambda: _self_join(["l.city = r.city"], chunk=2048),
    "link_only_cross_join": lambda: _two_frames("link_only", chunk=16),
    "link_and_dedupe_uid_mask": lambda: _two_frames("link_and_dedupe", chunk=4),
    "own_residual": lambda: _self_join(
        ["l.city = r.city and abs(l.age - r.age) <= 3"], chunk=8
    ),
    "previous_rule_residual": lambda: _self_join(
        ["l.city = r.city and l.dob != r.dob", "l.dob = r.dob"], chunk=8
    ),
    "derived_key_and_asymmetric_residual": lambda: _self_join(
        [
            "substr(l.surname, 1, 3) = substr(r.surname, 1, 3)",
            "l.city = r.city and length(l.surname) = length(r.surname)",
        ],
        chunk=64,
    ),
}


def _assert_pass_equals_oracle(program, plan, batch, mesh=None, **kw):
    """Drive the ids pass and hold every batch to the host oracle: the same
    row pair at EVERY position of the batch (the decode does not depend on
    the masks), the sentinel exactly where the oracle masks. Returns the
    number of batches and of unmasked pairs."""
    sentinel = program.n_patterns
    batches = seen = real = 0
    for r, p0, out_pos, n_valid, pid, il, ir in _virtual_pass_iter(
        program, plan, batch, mesh=mesh, **kw
    ):
        assert out_pos == seen
        assert len(pid) == len(il) == len(ir) == n_valid
        assert il.dtype == np.int32 and ir.dtype == np.int32
        q = p0 + np.arange(n_valid, dtype=np.int64)
        want_i, want_j, masked = decode_positions(plan, r, q)
        np.testing.assert_array_equal(il, want_i)
        np.testing.assert_array_equal(ir, want_j)
        np.testing.assert_array_equal(pid == sentinel, masked)
        batches += 1
        seen += n_valid
        real += int((~masked).sum())
    assert seen == plan.n_candidates
    return batches, real


@pytest.mark.parametrize("devices", [None, 4], ids=["one_device", "mesh_of_4"])
@pytest.mark.parametrize("kind", sorted(PLANS))
def test_kernel_pairs_equal_the_host_oracle(kind, devices):
    s, table, plan = PLANS[kind]()
    assert plan is not None and plan.n_candidates > 0
    if kind == "link_and_dedupe_uid_mask":
        assert plan.uid_codes is not None
    program = GammaProgram(s, table)
    mesh = make_mesh(devices) if devices else None
    # a batch that is no power of two and no multiple of the mesh: units are
    # split by batch edges and the last shard of a batch is padding
    batches, real = _assert_pass_equals_oracle(program, plan, 173, mesh=mesh)
    assert batches >= 2 and 0 < real <= plan.n_candidates
    # and what the pass keeps is what it yielded
    ids, counts, n_real = compute_virtual_pattern_ids(program, plan, 173, mesh=mesh)
    assert n_real == real == int((ids.pid != program.n_patterns).sum())
    base = 0
    for r, rp in enumerate(plan.rules):
        want_i, want_j, _ = decode_positions(
            plan, r, np.arange(rp.total, dtype=np.int64), compute_masked=False
        )
        np.testing.assert_array_equal(ids.il[base : base + rp.total], want_i)
        np.testing.assert_array_equal(ids.ir[base : base + rp.total], want_j)
        base += rp.total


def _jw_job():
    """A plan of Jaro-Winkler pairs of every kind: shared prefixes (close to
    and across the thresholds), token-equal names, unlike names, nulls."""
    n = 400
    rng = np.random.default_rng(5)
    names = np.array([f"prefix{i:04d}" for i in range(n)], dtype=object)
    base = np.array(["amelia", "amelie", "oliver", "olivia", "georgia", "", None],
                    dtype=object)
    some = rng.random(n) < 0.4
    names[some] = base[rng.integers(0, len(base), int(some.sum()))]
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": names,
            "city": np.array(["x", "y"], dtype=object)[rng.integers(0, 2, n)],
        }
    )
    s = _settings(
        ["l.city = r.city"],
        cols=[{
            "col_name": "name", "num_levels": 3,
            "comparison": {"kind": "jaro_winkler", "thresholds": [0.94, 0.88]},
        }],
    )
    table = encode_table(df, s)
    return s, table, build_virtual_plan(s, table, chunk=64)


@pytest.mark.parametrize("devices", [2, 4])
def test_one_chip_and_mesh_kernels_give_the_same_ids_and_histogram(devices):
    s, table, plan = _jw_job()
    program = GammaProgram(s, table)
    assert plan.n_candidates > 3 * 4096
    with StageTimer("gammas_patterns") as stage:
        batches, real = _assert_pass_equals_oracle(program, plan, 4096)
    assert batches >= 3
    # no batch is ever run twice, and nothing counts an overflow any more
    assert stage.counts["redo_positions"] == 0
    assert not [k for k in stage.counts if k.startswith("overflow") or k == "two_phase"]
    ids, counts, _ = compute_virtual_pattern_ids(program, plan, 4096)
    with StageTimer("gammas_patterns") as sharded:
        want, want_counts, _ = compute_virtual_pattern_ids(
            program, plan, 4096, mesh=make_mesh(devices))
    assert sharded.counts["redo_positions"] == 0
    for got, oracle in zip(ids, want):
        np.testing.assert_array_equal(got, oracle)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts.sum() == real
    assert len(np.unique(ids.pid)) == 4  # null and the three levels


def test_histogram_only_pass_equals_the_ids_pass_and_runs_each_batch_once():
    s, table, plan = _jw_job()
    program = GammaProgram(s, table)
    with StageTimer("gammas_patterns") as stage:
        ids, counts, n_real = compute_virtual_pattern_ids(
            program, plan, 4096, return_ids=False)
    batches = -(-plan.n_candidates // 4096)
    assert ids is None and n_real == counts.sum() > 0
    assert stage.counts["batches"] == batches >= 3  # once a batch
    assert stage.counts["redo_positions"] == 0 and stage.counts["hist_flushes"] == 1
    kept, with_ids, _ = compute_virtual_pattern_ids(program, plan, 4096)
    np.testing.assert_array_equal(counts, with_ids)
    assert n_real == int((kept.pid != program.n_patterns).sum())


@pytest.mark.parametrize("devices", [None, 4], ids=["one_device", "mesh_of_4"])
def test_histogram_only_pass_waits_at_the_flush_and_nowhere_else(devices):
    """Nothing of a batch comes home when no ids are wanted: the pass opens
    no ``d2h_wait`` span a batch, on one device as under a mesh, and its one
    fetch is the accumulator's (``flush_acc``)."""
    s, table, plan = _jw_job()
    program = GammaProgram(s, table)
    mesh = make_mesh(devices) if devices else None
    with StageTimer("gammas_patterns") as stage:
        got = list(_virtual_pass_iter(
            program, plan, 4096, mesh=mesh, want_ids=False,
            counts_out=np.zeros(program.n_patterns, np.int64)))
    assert len(got) >= 3 and all(t[4:] == (None, None, None) for t in got)
    waits = [t for t in spans() if t["name"] == "d2h_wait" and t["parent"] == stage.span["id"]]
    assert [t["counts"]["bytes"] for t in waits] == [4 * (program.n_patterns + 1)]
    assert stage.counts["hist_flushes"] == 1
    assert not [t for t in spans() if t["name"] == "mesh_gather" and t["t0"] >= stage.span["t0"]]


# ----------------------------------------------------------------------
# the scored frame
# ----------------------------------------------------------------------


def _linker_settings(link_type="dedupe_only", **over):
    return {
        "link_type": link_type,
        "comparison_columns": [
            {"col_name": "name", "num_levels": 2},
            {"col_name": "city", "num_levels": 2},
        ],
        "blocking_rules": ["l.surname = r.surname", "l.dob = r.dob"],
        "max_iterations": 3,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1024,  # the least the settings take
        **over,
    }


def _oracle_frame(linker):
    """The scored frame assembled from the HOST oracle's pairs: every
    position decoded and masked by ``decode_positions``, the pattern ids of
    the pairs left from the materialised pattern pass, scored and written
    by the linker's own tables and frame writer (one chunk a rule, into
    columns as long as the plan's positions: a bound, not the count)."""
    plan = linker._virtual
    program = linker._ensure_pattern_program()
    tables = linker._pattern_frame_tables()
    writer = _FrameWriter(linker, plan.n_candidates)
    for r, rp in enumerate(plan.rules):
        i, j, masked = decode_positions(
            plan, r, np.arange(rp.total, dtype=np.int64)
        )
        i, j = i[~masked], j[~masked]
        if not len(i):
            continue
        Pk, _ = program.compute_pattern_ids(i, j, batch_size=4096)
        writer.write(i, j, *tables, by=Pk.astype(np.int32))
    return writer.frame()


@pytest.mark.parametrize(
    "link_type,over",
    [
        ("dedupe_only", {}),
        ("dedupe_only", {"virtual_materialise_ids": "off"}),
        ("dedupe_only", {"virtual_materialise_ids": "off", "mesh": {"data": 4}}),
        ("dedupe_only", {"mesh": {"data": 4}}),
        ("link_and_dedupe", {}),
        ("link_and_dedupe", {"virtual_materialise_ids": "off"}),
    ],
    ids=["stored", "recompute", "recompute_mesh", "stored_mesh",
         "stored_two_frames", "recompute_two_frames"],
)
def test_scored_frame_equals_one_assembled_from_the_oracles_pairs(link_type, over):
    df = _people(400, seed=37)
    frames = {"df": df}
    if link_type != "dedupe_only":
        df_r = df.iloc[250:].assign(unique_id=lambda d: d["unique_id"] - 200)
        frames = {"df_l": df.iloc[:250], "df_r": df_r}
    linker = Splink(_linker_settings(link_type, **over), **frames)
    stored = over.get("virtual_materialise_ids") != "off"
    kept_mid_stream = []
    stream = linker._iter_pattern_triples

    def watched():
        kept_mid_stream.append(linker._P_virtual is not None)
        return stream()

    linker._iter_pattern_triples = watched
    frame = linker.get_scored_comparisons()
    assert kept_mid_stream == [stored]  # the stream the case names ran
    assert linker._P_virtual is None  # ids and pairs released with the frame
    assert 3 * 1024 < len(frame) < linker._virtual.n_candidates
    pd.testing.assert_frame_equal(frame, _oracle_frame(linker))
    # the stream decoded nothing on the host: every position's pair came
    # from the kernel
    decodes = [s["counts"] for s in spans(run=linker.run_id)
               if s["name"] == "decode_pairs"]
    assert sum(c["rows"] for c in decodes) == linker._virtual.n_candidates
    assert sum(c["kept"] for c in decodes) == len(frame)
    assert all(c["device_decoded"] == c["rows"] for c in decodes)


@pytest.mark.parametrize("generation", ["on", "off"])
def test_zero_row_frame_is_typed_like_a_frame_with_pairs(generation):
    """No candidate at all (every key unique) gives the zero-row frame, and
    its columns carry the dtypes the same job's frame has when pairs exist:
    a retained column is typed from the WHOLE input column, not from the
    (here empty) subset of it that a frame holds."""
    df = _people(400, seed=41)
    df["key"] = [f"k{k}" for k in range(len(df))]
    over = {"device_pair_generation": generation,
            "additional_columns_to_retain": ["surname", "age"]}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        empty = Splink(
            _linker_settings(blocking_rules=["l.key = r.key"], **over), df=df
        ).get_scored_comparisons()
    full = Splink(_linker_settings(**over), df=df).get_scored_comparisons()
    assert len(empty) == 0 and len(full) > 1024
    pd.testing.assert_series_equal(empty.dtypes, full.dtypes)
    for column in ("name_l", "city_r", "surname_l"):
        assert isinstance(empty[column].dtype, pd.StringDtype)
    assert empty["age_r"].dtype == np.float64


def test_no_host_decode_left_in_the_program():
    """``decode_positions`` is the oracle: nothing under splink_tpu/ calls
    it but its own module."""
    import pathlib

    import splink_tpu

    root = pathlib.Path(splink_tpu.__file__).parent
    callers = [
        str(p.relative_to(root)) for p in sorted(root.rglob("*.py"))
        if "decode_positions" in p.read_text() and p.name != "pairgen.py"
    ]
    assert callers == []


# ----------------------------------------------------------------------
# the pass nobody wants ids from
# ----------------------------------------------------------------------


@pytest.mark.parametrize("mesh", [None, {"data": 4}], ids=["one_device", "mesh_of_4"])
def test_em_only_pass_downloads_nothing_per_pair(mesh):
    over = {"mesh": mesh} if mesh else {}
    linker = Splink(_linker_settings(**over), df=_people(400, seed=37))
    linker.estimate_parameters()
    assert linker._P_virtual is None
    table = spans(run=linker.run_id)
    [stage] = [s for s in table if s["name"] == "gammas_patterns"]
    candidates = linker._virtual.n_candidates
    assert stage["counts"]["batches"] >= 3 and candidates > 1000

    def under(s):
        by_id = {t["id"]: t for t in table}
        while s["parent"] is not None:
            s = by_id[s["parent"]]
            if s["id"] == stage["id"]:
                return True
        return False

    # what came home during the pass: the histogram accumulator, whose size
    # is the pattern space's and not the pairs', once — no wait a batch, on
    # one device as under a mesh
    program = linker._ensure_pattern_program()
    waits = [s["counts"]["bytes"] for s in table
             if s["name"] == "d2h_wait" and under(s)]
    assert waits == [4 * (program.n_patterns + 1)]
    assert sum(waits) < candidates  # less than a byte a pair, all told
    assert stage["counts"]["ids_kept"] == 0
    assert stage["counts"]["redo_positions"] == 0
    assert "overflow_batches" not in stage["counts"]
    assert not [s for s in table if s["name"] == "mesh_gather"]
    assert not [s for s in table if s["name"] == "decode_pairs"]


def test_histogram_pass_yields_no_per_pair_arrays():
    s, table, plan = PLANS["self_join_triangles_and_rectangles"]()
    program = GammaProgram(s, table)
    counts = np.zeros(program.n_patterns, np.int64)
    got = list(_virtual_pass_iter(
        program, plan, 173, want_ids=False, counts_out=counts
    ))
    assert got and all(t[4:] == (None, None, None) for t in got)
    ids, want_counts, n_real = compute_virtual_pattern_ids(
        program, plan, 173, return_ids=False
    )
    assert ids is None and n_real == counts.sum()
    np.testing.assert_array_equal(counts, want_counts)
