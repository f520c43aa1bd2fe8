"""The sharded virtual pattern kernel compiled for a DESCRIBED four-chip TPU
v5e host, no chip attached (``on-chip-measurement`` guide, section 2).

The CPU mesh tests cannot see what the TPU's compiler does with this kernel:
there the string kernels are Pallas (Mosaic) custom calls, which XLA's
partitioner does not split, so the body has to sit under ``shard_map`` or
every chip is handed every pair. This is the cell ``c4_dedupe_mesh4``'s
program (configuration ``baseline_c4_v5e4``) at a small table: it has to
compile for ``v5e:2x2`` with one all-reduce (the histogram's psum) and no other
collective, every Mosaic call fed ``batch / 4`` pairs, and scratch that
divides by four against the same kernel on one chip.

One file, one process: only one process at a time may load the TPU's library,
so the topology is described inside a fixture and nowhere at import.
"""

import copy
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROWS, BATCH = 40_000, 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def small_job(name):
    """(program, plan) of a configuration's model at a small table."""
    from chipbench import datagen
    from splink_tpu import Splink

    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        config = json.load(f)
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    people = datagen.make_people(rows=ROWS, seed=config["generator"]["population_seed"], **gen)
    settings = copy.deepcopy(config["settings"])
    settings.update(pair_batch_size=BATCH, max_resident_pairs=1024)
    settings.pop("mesh", None)  # the linker's own mesh would be of CPU devices
    linker = Splink(settings, df=people)
    linker._ensure_encoded()
    return linker._ensure_pattern_program(), linker._virtual_plan()


@pytest.fixture(scope="module")
def job():
    """The four-chip deployment's model."""
    return small_job("baseline_c4_v5e4")


def compile_rule(program, plan, mesh, sharding_of, monkeypatch):
    """The first rule's kernel, lowered from shapes alone and compiled for the
    devices of ``sharding_of`` (sharded, replicated)."""
    from splink_tpu import pairgen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the Pallas forms, as on the chip
    shard, repl = sharding_of
    rp = plan.rules[0]
    rule_bs = min(BATCH, 1 << max((rp.total - 1).bit_length(), 6))
    meta = pairgen._unit_batch_meta(rp.pc, rp.total, rule_bs)[0][2]
    fn = pairgen._build_virtual_pattern_fn(
        program._parts, n_prev=0, has_uid_mask=plan.uid_codes is not None,
        own_res=rp.residual_fn, prev_res=(), mesh=mesh)

    def shape(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    uid = plan.uid_codes if plan.uid_codes is not None else np.zeros(1, np.int32)
    args = (jax.ShapeDtypeStruct((rule_bs,), jnp.int32, sharding=shard),
            shape(program._packed, repl), shape(rp.order, repl),
            *(shape(a, repl) for a in (rp.ua, rp.la, rp.ub, rp.lb)),
            shape(plan.codes, repl), shape(uid, repl),
            tuple(shape(a, repl) for a in plan.res_ops), shape(meta, repl),
            jax.ShapeDtypeStruct((program.n_patterns + 1,), jnp.int32, sharding=repl))
    # the suite runs with x64 on (conftest) and Mosaic takes no int64 index:
    # the program's own processes run without it, as the chip does
    with jax.enable_x64(False):
        return rule_bs, fn.lower(*args).compile()


def test_sharded_pattern_kernel_compiles_for_four_chips(topo, uncached, job, monkeypatch):
    program, plan = job
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    four = (NamedSharding(mesh, PartitionSpec("data")), NamedSharding(mesh, PartitionSpec()))
    rule_bs, compiled = compile_rule(program, plan, mesh, four, monkeypatch)
    assert rule_bs == BATCH  # the rule fills the batch: scratch is the batch's
    hlo = compiled.as_text()
    collectives = re.findall(r"\s(all-gather|all-reduce|all-to-all|collective-permute|"
                             r"reduce-scatter)(?:-start)?\(", hlo)
    assert collectives == ["all-reduce"], collectives  # the histogram's psum, nothing else
    calls = [ln for ln in hlo.splitlines() if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls, "no Mosaic call: the string kernels fell off their Pallas forms"
    for ln in calls:
        dims = {int(d) for dims in re.findall(r"\[([\d,]+)\]", ln.split("custom_call_target")[0])
                for d in dims.split(",")}
        assert rule_bs not in dims and rule_bs // 4 in dims, ln[:300]

    one = SingleDeviceSharding(topo.devices[0])
    _, single = compile_rule(program, plan, None, (one, one), monkeypatch)
    sharded_temp = compiled.memory_analysis().temp_size_in_bytes
    single_temp = single.memory_analysis().temp_size_in_bytes
    # each chip holds a quarter of the batch's scratch (and a whole table)
    assert sharded_temp < 0.35 * single_temp, (sharded_temp, single_temp)
    # one chip runs the body the mesh runs: the same Mosaic calls (the three
    # Jaro-Winkler columns), each fed the whole batch, from a 30-word row
    single_calls = [ln for ln in single.as_text().splitlines()
                    if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(single_calls) == len(calls) == 3, (len(single_calls), len(calls))
    assert program._packed.shape[1] == 30
    assert f"u32[{program._packed.shape[0]},30]" in single.as_text()


def test_case_library_kernel_compiles_for_one_chip(topo, uncached, monkeypatch):
    """The cell ``c4lib_dedupe_virtual``'s program (configuration
    ``c4_case_library``): four Jaro-Winkler evaluations (two name inversions,
    self and cross pair, the cross pair padded to a common width) and three
    Levenshtein ones, each a Mosaic call fed the whole batch."""
    program, plan = small_job("c4_case_library")
    one = SingleDeviceSharding(topo.devices[0])
    rule_bs, compiled = compile_rule(program, plan, None, (one, one), monkeypatch)
    assert rule_bs == BATCH
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 7, len(calls)


def test_small_table_gathers_rows_in_real_bytes(topo, uncached, monkeypatch):
    """The cell ``c2_dedupe_cartesian``'s gamma program (configuration
    ``baseline_c2``: two Jaro-Winkler columns, a 12-word row, 10,000 rows).
    From a table that small the TPU's compiler writes every gathered row
    padded to 128 lanes — over 1,500 B of scratch a pair position, so no batch
    above 2^23 fitted the chip — and from the table as ``GammaProgram`` uploads
    it on a TPU (``gammas._device_table``: rows up to ``_MIN_TPU_TABLE_ROWS``)
    it gathers column-major like the large tables of the other cells: under
    400 B a position. If the first reading ever falls, the floor can go."""
    from chipbench import datagen
    from splink_tpu import Splink, gammas

    with open(os.path.join(ROOT, "chipbench", "configs", "baseline_c2.json")) as f:
        config = json.load(f)
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    rows = config["generator"]["rows"]
    people = datagen.make_people(rows=rows, seed=config["generator"]["population_seed"], **gen)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the table and kernels of the chip
    settings = copy.deepcopy(config["settings"])
    with pytest.warns(UserWarning, match="quadratic"):
        linker = Splink(settings, df=people)
    linker._ensure_encoded()
    program = linker._ensure_pattern_program()
    lanes = program._packed.shape[1]
    assert program._packed.shape == (gammas._MIN_TPU_TABLE_ROWS, 12)
    assert not np.asarray(program._packed[rows:]).any()  # rows nobody indexes
    one = SingleDeviceSharding(topo.devices[0])
    batch = 1 << 20
    fn = gammas._jit_gamma_batch(program._parts)

    def scratch_per_position(table_rows):
        args = (jax.ShapeDtypeStruct((table_rows, lanes), jnp.uint32, sharding=one),
                jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one))
        with jax.enable_x64(False):
            compiled = fn.lower(*args).compile()
        calls = [ln for ln in compiled.as_text().splitlines()
                 if "custom-call(" in ln and "tpu_custom_call" in ln]
        assert len(calls) == 2, len(calls)  # the two Jaro-Winkler columns
        return compiled.memory_analysis().temp_size_in_bytes / batch

    assert scratch_per_position(rows) > 1500
    assert scratch_per_position(program._packed.shape[0]) < 400
