"""Falsifiability of the serve-kernel analysis gates: the registered serve
kernels are clean (test_codebase_clean covers the full registries), and
each NEW gate can actually fail — a broken twin of every serve kernel
trips its invariant, so the gates are tests, not decorations."""

import numpy as np
import pytest

from splink_tpu.analysis.shard_audit import (
    ShardKernelSpec,
    audit_shard_kernel,
    register_shard_kernel,
    run_shard_audit,
)
from splink_tpu.analysis.trace_audit import (
    KernelSpec,
    audit_kernel,
    run_audit,
)


def test_serve_kernels_registered_and_clean():
    findings, audited = run_audit(
        ["serve_encode_query", "serve_candidate_gather", "serve_score_topk"]
    )
    assert audited == 3
    assert not findings, "\n".join(f.format() for f in findings)


def test_serve_shard_kernel_registered_and_clean():
    findings, audited = run_shard_audit(["serve_score_topk_sharded"])
    assert audited == 1
    assert not findings, "\n".join(f.format() for f in findings)


def test_bad_serve_kernel_trips_ta_const():
    """A score kernel that CLOSES OVER the packed reference table (instead
    of taking it as an argument) embeds it as a jaxpr constant — the
    serialised-into-every-compile hazard TA-CONST exists to catch."""

    def build():
        import jax.numpy as jnp

        from splink_tpu.analysis.trace_audit import shared_gamma_program
        from splink_tpu.serve.engine import make_score_topk_fn

        program = shared_gamma_program()
        score = make_score_topk_fn(
            program._layout, program.settings["comparison_columns"], k=4
        )
        big = jnp.tile(program._packed, (4096, 1))  # > 64 KiB constant

        def bad(packed_q, cand, valid, params):
            return score(packed_q, big, cand, valid, params)

        from splink_tpu.analysis.trace_audit import shared_fs_inputs

        _, params = shared_fs_inputs()
        packed_q = jnp.zeros((16, program._packed.shape[1]), jnp.uint32)
        cand = jnp.zeros((16, 8), jnp.int32)
        valid = jnp.zeros((16, 8), bool)
        return bad, (packed_q, cand, valid, params), {}

    spec = KernelSpec(name="bad_serve_score_const", build=build)
    findings = audit_kernel(spec)
    assert any(f.rule == "TA-CONST" for f in findings), [
        f.format() for f in findings
    ]


def test_bad_serve_gather_trips_ta_dtype():
    """An unpinned arange in the candidate decode goes int64 under the
    forced-x64 trace — the dtype leak TA-DTYPE exists to catch."""

    def build():
        import jax.numpy as jnp

        def bad(qbuckets, sizes):
            slot = jnp.arange(16)  # unpinned: int64 under x64
            cnt = sizes[jnp.where(qbuckets >= 0, qbuckets, 0)]
            return (slot[None, :] < cnt[:, None]).sum(
                axis=1, dtype=jnp.int32
            )

        qb = jnp.zeros(8, jnp.int32)
        sizes = jnp.ones(4, jnp.int32)
        return bad, (qb, sizes), {}

    spec = KernelSpec(name="bad_serve_gather_dtype", build=build)
    findings = audit_kernel(spec)
    assert any(f.rule == "TA-DTYPE" for f in findings), [
        f.format() for f in findings
    ]


def test_bad_serve_shard_twin_trips_the_gate():
    """The serving shard gate is falsifiable: a twin that takes
    lax.top_k (the unpartitionable op the production kernel deliberately
    avoids) and then reorders the query rows by their best score — a
    computed-index gather of the sharded operand, what the production
    kernel's static repeat avoids — brings back the all-gather and hands
    the (query, k) outputs back replicated: SA-COLL and SA-SPEC both fire.
    (lax.top_k alone trips only SA-COLL under the installed XLA, which
    reshards its outputs after the gathered sort.)"""
    registry: dict = {}

    @register_shard_kernel(
        "bad_serve_topk_sharded", n_pairs=64, registry=registry
    )
    def _build():
        import jax
        import jax.numpy as jnp

        from splink_tpu.analysis.shard_audit import audit_mesh
        from splink_tpu.parallel.mesh import pair_sharding

        mesh = audit_mesh()
        scores = jax.device_put(
            np.zeros((64, 8), np.float32), pair_sharding(mesh)
        )

        def bad(scores):
            top_p, top_i = jax.lax.top_k(scores, 4)
            order = jnp.argsort(-top_p[:, 0])
            return top_p[order], top_i[order]

        return bad, (scores,), {}

    findings, audited = run_shard_audit(registry=registry, baselines={})
    assert audited == 1
    fired = {f.rule for f in findings}
    assert "SA-COLL" in fired and "SA-SPEC" in fired, [
        f.format() for f in findings
    ]


def test_shard_budget_drift_fails_for_serve_kernel():
    """Cost-budget drift on the serving kernel renders the diff-style
    message (the same contract the EM kernels have)."""
    from splink_tpu.analysis.shard_audit import (
        SHARD_REGISTRY,
        _ensure_default_registry,
        load_baselines,
    )

    _ensure_default_registry()
    baseline = dict(
        load_baselines()["kernels"]["serve_score_topk_sharded"]
    )
    baseline["flops"] = float(baseline["flops"]) * 10
    findings = audit_shard_kernel(
        SHARD_REGISTRY["serve_score_topk_sharded"], baseline
    )
    rendered = "\n".join(f.format() for f in findings)
    assert "flops: baseline" in rendered and "measured" in rendered
