"""CP-attention comm scoreboard: prove the overlap-pipelined ulysses and
head-replicated MQA paths move exactly the bytes the comm model says,
from compiled post-SPMD HLO.

Two claims, each asserted via a declarative gate file (a regression
fails the bench, and CI):

* **overlap-pipelined ulysses** (gate ``cp_overlap``) — with
  ``overlap_chunks = c`` the K/V all-to-alls split into ``c`` per-chunk
  collectives: a2a count goes 4 → 2 + 2c, the smallest a2a payload
  shrinks ÷c, and total wire bytes are unchanged (the merge is
  online-softmax-exact, so comm granularity is the *only* change).
  XLA's collective-combiner passes must not have re-merged the chunks.
* **head-replicated MQA ulysses** (gate ``ulysses_mqa``) — at a shape
  where ``KV % cp != 0`` (H=8, KV=4, cp=8), replicating KV heads
  r = cp/gcd(KV, cp) = 2× and running plain ulysses moves half the wire
  bytes of the all-gather fallback, through all-to-alls only.

The analytic model (``repro.roofline.analysis.cp_attention_comm``) is
additionally calibrated against the measured HLO wire totals of all
four programs (±2%), so roofline projections for real shapes rest on a
model the compiler has countersigned.

Run via ``python benchmarks/run.py --cp-attention`` (subprocess with 8
virtual devices); the JSON lands in ``BENCH_cp_attention.json`` at the
repo root.  Numbers are per-device ring-model bytes (post-SPMD HLO).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_gates
from repro.dist import context as cpx
from repro.roofline import analysis as ra
from repro.launch.mesh import make_mesh

B, S, H, KV, D = 2, 64, 8, 4, 16
CHUNKS = 4


def make_qkv(seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    return q, k, v


def cp_hlo(cp: int, mode: str, chunks: int = 1) -> str:
    """Post-SPMD HLO of one cp_attention forward on a (cp,)-device
    ``seq`` mesh.  impl='ref' — the gates assert collective structure,
    which the in-shard kernel tier does not change."""
    mesh = make_mesh((cp,), ("seq",))
    q, k, v = make_qkv()
    f = jax.jit(functools.partial(
        cpx.cp_attention, mesh=mesh, mode=mode, impl="ref",
        overlap_chunks=chunks, block_q=16, block_kv=16))
    with mesh:
        return f.lower(q, k, v).compile().as_text()


def _gate(name: str, programs: dict, symbols=None) -> dict:
    rep, measured = hlo_gates.evaluate_file(
        hlo_gates.GATES_DIR / f"{name}.json", programs, symbols=symbols)
    rep.raise_on_error(AssertionError)
    return measured


def _hlo_wire(text: str) -> float:
    return sum(ra.wire_bytes_by_dtype(text).values())


def _model_wire(mode: str, cp: int, chunks: int = 1) -> float:
    return ra.cp_attention_comm(mode, H=H, KV=KV, D=D, cp=cp, B=B, S=S,
                                itemsize=4, overlap_chunks=chunks
                                )["wire_bytes"]


def _calibrate(label: str, mode: str, cp: int, text: str,
               chunks: int = 1) -> dict:
    """Model wire bytes must match the compiled program's within 2%."""
    model = _model_wire(mode, cp, chunks)
    hlo = _hlo_wire(text)
    assert abs(hlo / model - 1.0) <= 0.02, (
        f"{label}: comm model predicts {model:g} wire B but the "
        f"compiled HLO moves {hlo:g}")
    return {"model_wire_bytes": model, "hlo_wire_bytes": hlo}


def overlap_claim() -> dict:
    """Chunked K/V a2as: count 2+2c, min payload ÷c, wire constant
    (gate: cp_overlap)."""
    cp = 4
    mono = cp_hlo(cp, "ulysses", 1)
    over = cp_hlo(cp, "ulysses", CHUNKS)
    m = _gate("cp_overlap", {"mono": mono, "overlap": over},
              symbols={"chunks": CHUNKS,
                       "overlap_a2as": 2 + 2 * CHUNKS})
    return {"cp": cp, "chunks": CHUNKS,
            "a2a_count_mono": m["mono_a2a_count"],
            "a2a_count_overlap": m["overlap_a2a_count"],
            "min_payload_ratio": m["min_payload_div_chunks"],
            "wire_ratio": m["wire_upper"],
            "mono": _calibrate("mono", "ulysses", cp, mono),
            "overlap": _calibrate("overlap", "ulysses", cp, over, CHUNKS)}


def mqa_claim() -> dict:
    """Head-replicated ulysses halves wire vs the all-gather fallback at
    KV % cp != 0 (gate: ulysses_mqa)."""
    cp = 8
    mqa = cp_hlo(cp, "ulysses_mqa")
    ag = cp_hlo(cp, "allgather")
    m = _gate("ulysses_mqa", {"mqa": mqa, "allgather": ag})
    import math
    return {"cp": cp, "kv_replication": cp // math.gcd(KV, cp),
            "wire_ratio_vs_allgather": m["mqa_wire_vs_allgather"],
            "a2a_count": m["mqa_a2a_count"],
            "model_ratio": (_model_wire("ulysses_mqa", cp)
                            / _model_wire("allgather", cp)),
            "mqa": _calibrate("mqa", "ulysses_mqa", cp, mqa),
            "allgather": _calibrate("allgather", "allgather", cp, ag)}


def main() -> None:
    out = {"shape": {"B": B, "S": S, "H": H, "KV": KV, "D": D,
                     "itemsize": 4},
           "overlap": overlap_claim(),
           "mqa": mqa_claim()}
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
