"""The job's regime: which pair source feeds it, in which form its gammas
live, which EM loop trains it and whether the virtual pass keeps its ids —
one pure function, ``decide``, of what the linker knows (no JAX, no I/O).
The four settings keys that steer it — ``max_resident_pairs`` (R below),
``device_pair_generation``, ``virtual_materialise_ids`` and
``overlap_blocking`` — are read here and nowhere else.

The linker asks for one outcome at a time, when it needs it
(``Splink._regime``). A fact it has not learnt yet is None in ``Facts``; an
outcome that reads one is a ``Wait`` naming it, and the linker learns it
then — encodes the table, estimates the pair bound, builds the virtual plan,
blocks, probes free RAM — and asks again. An outcome reads only the facts
its rule names, so a fact that arrives later never changes it.

The table (where an outcome has rows, the first that applies wins):

virtual: build the virtual pair index's plan (pairs decoded on the device;
    a rule the plan cannot take still gives host blocking)
    no    ``device_pair_generation`` "off"; the model not pattern-capable;
          ``approx_blocking`` has columns to sketch (the index has no
          approximate tier: every approximate pair would be lost)
    yes   "on"; "auto" where the pair bound > R
pairs: materialised pairs, where no plan was built
    "spill"  ``build_spill_dir`` set and no approximate tier to run:
             sharded emission into the store, resumable, so no overlap
             (a resumed build skips the segments it committed)
    "host"   otherwise
overlap: host blocking feeds a device consumer as it joins
    (``overlap_blocking``)
stream: that consumer, "patterns" (a PatternStream) where the pair bound
    > R and the model is pattern-capable, else "gammas" (a GammaStream)
keep_limit: the GammaStream keeps its device batches up to R pairs, 0 under
    a mesh (which shards its own upload)
shard_patterns: pattern passes shard over a mesh on one controller; on many
    (the sharded pass is a single-controller layout) each host runs the
    whole pass on its own device
patterns: the pattern-id pipeline (EM on the pattern histogram, scores from
    per-pattern tables)
    yes   a virtual plan was built
    no    not pattern-capable; a multi-controller spill store (its pair
          count is this process's share: a choice by count could put one
          controller in a collective and another not, a deadlock)
    the pairs > R, otherwise
gammas: a gamma matrix the overlap did not keep is "decode"d from the
    pattern ids host blocking streamed (bit-identical: the id IS the gamma
    vector in mixed radix), else "compute"d on the device
keep_device: the computed matrix stays on the device for EM: pairs ≤ R and
    no mesh
em: how ``estimate_parameters`` trains
    "patterns"  the pattern-id pipeline: fused EM, the counts as weights
    "spill"     a spill store, multi-controller (the one path that reduces
                across processes) or past R: streamed over the manifest,
                no G on the host
    "gammas"    otherwise
em_resident: EM over a gamma matrix of n rows is resident and fused (a
    device OOM falls back to streamed) where n ≤ R, else streamed over G
ids_kept: the virtual pass brings home each candidate's pattern id and row
    pair, so the score stream after EM needs no second device pass
    yes / no  ``virtual_materialise_ids`` "on" / "off"
    "auto"    yes where a score stream follows and the ids (10 B a
              candidate with uint16 ids, else 12) fit both
              ``MAX_RESIDENT_ID_BYTES`` and half the free host RAM
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, NamedTuple

MAX_RESIDENT_ID_BYTES = 1 << 33  # 8.6 GB


class Wait(NamedTuple):
    """An outcome not decided yet: the fact it waits on."""

    fact: str


class _Unknown(Exception):
    pass


@dataclass(frozen=True)
class Facts:
    """What the linker knows of one job; None where it has not learnt it."""

    settings: Mapping  # the completed settings
    # no custom comparison kernel (one could emit gammas outside [-1, L-1]
    # and alias pattern ids), and a pattern space whose dense device
    # accumulators fit their bytes (gammas.pattern_space_fits)
    pattern_capable: bool
    mesh: bool = False
    process_count: int = 1
    approx_columns: bool | None = None  # approx_blocking's tier has columns
    pair_bound: int | None = None  # blocking.estimate_pair_upper_bound
    plan: bool | None = None  # a virtual plan was built
    n_candidates: int | None = None  # its candidate positions
    ids_fit_uint16: bool = False
    n_pairs: int | None = None  # materialised pairs, or a gamma matrix's rows
    spill_store: bool = False
    multihost_store: bool = False
    score_follows: bool = False
    free_ram: float | None = None  # bytes; inf where the platform has no probe


def decide(f: Facts) -> dict:
    """The module docstring's table, row for row: every outcome by name, a
    value or a ``Wait``."""
    s = f.settings
    R = int(s["max_resident_pairs"])
    overlap = bool(s.get("overlap_blocking", True))

    def known(fact):
        if getattr(f, fact) is None:
            raise _Unknown(fact)
        return getattr(f, fact)

    def approx():  # approx_blocking's tier has columns to sketch
        return bool(s.get("approx_blocking")) and known("approx_columns")

    def virtual():
        mode = s.get("device_pair_generation", "auto")
        if mode == "off" or not f.pattern_capable or approx():
            return False
        return mode == "on" or known("pair_bound") > R

    def stream():
        return "patterns" if known("pair_bound") > R and f.pattern_capable else "gammas"

    def patterns():
        if known("plan") or not f.pattern_capable:
            return f.plan
        return not f.multihost_store and known("n_pairs") > R

    def gammas():
        known("n_pairs")  # the spill store is known with the pairs
        overlapped = overlap and not f.spill_store
        return "decode" if overlapped and stream() == "patterns" else "compute"

    def em():
        if patterns():
            return "patterns"
        n = known("n_pairs")  # the spill store is known with the pairs
        return "spill" if f.spill_store and (f.multihost_store or n > R) else "gammas"

    def ids_kept():
        ids = s.get("virtual_materialise_ids", "auto")
        if ids != "auto":
            return ids == "on"
        if not f.score_follows or not known("plan"):
            return False
        need = f.n_candidates * (10 if f.ids_fit_uint16 else 12)
        return need <= MAX_RESIDENT_ID_BYTES and need <= known("free_ram") / 2

    rules = dict(
        virtual=virtual,
        pairs=lambda: "spill" if s.get("build_spill_dir") and not approx() else "host",
        overlap=lambda: overlap,
        stream=stream,
        keep_limit=lambda: 0 if f.mesh else R,
        shard_patterns=lambda: f.mesh and f.process_count == 1,
        patterns=patterns,
        gammas=gammas,
        keep_device=lambda: known("n_pairs") <= R and not f.mesh,
        em=em,
        em_resident=lambda: known("n_pairs") <= R,
        ids_kept=ids_kept,
    )
    out = {}
    for outcome, rule in rules.items():
        try:
            out[outcome] = rule()
        except _Unknown as wait:
            out[outcome] = Wait(*wait.args)
    return out
