"""The comparison that decides ``correct`` for a job whose scored pairs leave
as CHUNKS (``runners/job_stream.py``): ``correct.compare`` on the one frame
the checked job's chunks make end to end — a chunk dropped or handed out twice
is ``pairs_wrong`` there — plus three numbers only the chunks can give, each
held at 0 by the configuration's ``guarantees.stream``:

``chunks_oversize``       chunks longer than ``pair_batch_size``;
``chunk_schema_differs``  chunks whose columns or dtypes are not the first's;
``chunks_empty``          zero-row chunks handed out.

``verdict`` is ``correct.py``'s own; ``stand_in`` is its stand-in handed out
as one chunk of its own length (a control has no chunks to fault).
"""

from __future__ import annotations

from chipbench import correct
from chipbench.correct import verdict  # noqa: F401


def schema(frame) -> list:
    """A chunk's columns and dtypes, as plain data."""
    return [[name, str(dtype)] for name, dtype in frame.dtypes.items()]


def compare(produced: dict, prep: dict) -> dict:
    """``correct.compare``'s numbers, then the stream's three. ``produced``
    holds beside the joined ``frame``: ``chunk_rows`` and ``chunk_schemas``
    (each chunk's length, and its ``[column, dtype]`` rows, in the order
    handed out) and ``pair_batch_size``."""
    out = correct.compare(produced, prep)
    rows, schemas = produced["chunk_rows"], produced["chunk_schemas"]
    out["chunks_oversize"] = sum(1 for n in rows if n > produced["pair_batch_size"])
    out["chunk_schema_differs"] = sum(1 for s in schemas if s != schemas[0])
    out["chunks_empty"] = sum(1 for n in rows if n == 0)
    return out


def stand_in(ref: dict, uid: str = "unique_id") -> dict:
    out = correct.stand_in(ref, uid)
    frame = out["frame"]
    return {**out, "chunk_rows": [len(frame)], "pair_batch_size": len(frame),
            "chunk_schemas": [schema(frame)]}
