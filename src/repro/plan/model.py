"""Machine model + analytic costs for every executable sketch/Nyström variant.

The paper's cost model (§3) counts *words moved per processor* in the
alpha-beta model; the repo's entry points add two more resources a real
dispatcher must price: local FLOPs and HBM traffic (the fused Pallas kernel
trades HBM words for in-VMEM regeneration the same way Alg. 1 trades network
words for it).  This module turns all of that into one comparable unit —
predicted seconds on a :class:`MachineModel` — while keeping the raw words /
flops / bytes visible so tests can assert the paper's closed forms exactly.

Per-variant analytic costs:

  * ``alg1_cost``        — Alg. 1 on a (p1, p2, p3) grid: words are exactly
                           ``core.grid.alg1_bandwidth_words``.
  * ``alg2_cost``        — Alg. 2 on (p, q) grids: words are exactly
                           ``core.grid.alg2_bandwidth_words``.
  * ``alg2_fused_cost``  — the single-jit two-grid form
                           (``nystrom_two_grid_fused``): same stage terms,
                           but the cross-mesh nr/P Redistribute becomes the
                           in-program layout min-cut
                           (``fused_redistribute_words``).
  * ``local_cost``       — single-device GEMM with Omega materialized in HBM.
  * ``pallas_fused_cost``— the fused kernel: Omega never touches HBM, so the
                           memory term drops by n2·r words (the §6.3 claim
                           applied to the memory hierarchy).
  * ``stream_update_cost``— one row-slab ingest step of the streaming
                           subsystem (local or sharded).

``alg1_cost`` / ``alg2_cost`` / ``stream_update_cost`` take a ``backend``
("jnp" | "pallas") pricing the *local* GEMM body: the pallas backend
(kernels/local.py) generates Omega/Psi blocks in VMEM, zeroing their HBM
streams and halving the accumulate round trips — identical network words,
strictly fewer HBM words, which is how ``plan_*`` picks the backend
analytically (``hbm_roofline_words`` is the single-GEMM table).

Machine presets are deliberately coarse (vendor peaks); the measured
autotuner (``plan.autotune``) exists precisely because these numbers are
only good enough to *rank* candidates, not to predict wall time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from repro.core.grid import (
    alg1_bandwidth_words,
    alg1_latency_hops,
    alg2_bandwidth_words,
)
from repro.roofline.analysis import HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16


# ---------------------------------------------------------------------------
# Machine model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Alpha-beta-gamma machine: network latency/bandwidth + compute/memory.

    alpha      : per-message latency (seconds)
    byte_bw    : interconnect bandwidth per device (bytes/s) — 1/beta
    flop_rate  : peak FLOP/s per device
    hbm_bw     : HBM bandwidth per device (bytes/s)
    vmem_bytes : per-core fast scratch (VMEM) capacity
    hbm_bytes  : per-device main memory capacity
    supports_pallas : whether the fused Mosaic/Pallas kernels can run
                      natively (TPU); elsewhere they only run in interpret
                      mode, which is a correctness tool, not a fast path.
    dispatch_overhead : host-side cost of launching ONE compiled update
                      (python + runtime + launch latency, seconds).  This
                      is the term shape-bucketed ragged ingest amortizes:
                      N streams fused into one bucket pay it once instead
                      of N times, at the price of padded-lane FLOPs/HBM —
                      :func:`choose_bucket_edges` trades the two.
    """
    name: str
    alpha: float
    byte_bw: float
    flop_rate: float
    hbm_bw: float
    vmem_bytes: int
    hbm_bytes: int
    supports_pallas: bool = False
    dispatch_overhead: float = 5e-5


# Per-chip vendor peaks; the v5e numbers are the roofline module's
# constants, so the planner and the measured roofline agree by construction.
PRESETS = {
    "tpu_v5e": MachineModel(
        name="tpu_v5e", alpha=1e-6, byte_bw=ICI_LINK_BW,
        flop_rate=PEAK_FLOPS_BF16, hbm_bw=HBM_BW,
        vmem_bytes=128 * 2 ** 20, hbm_bytes=16 * 2 ** 30,
        supports_pallas=True),
    "tpu_v4": MachineModel(
        name="tpu_v4", alpha=1e-6, byte_bw=100e9, flop_rate=275e12,
        hbm_bw=1200e9, vmem_bytes=128 * 2 ** 20, hbm_bytes=32 * 2 ** 30,
        supports_pallas=True),
    # Host CPU (also XLA's fake multi-device backend): "network" is shared
    # memory, flops a few-core GEMM rate.  Order-of-magnitude is all the
    # planner needs — candidates are re-ranked by the autotuner anyway.
    "cpu": MachineModel(
        name="cpu", alpha=5e-6, byte_bw=10e9, flop_rate=5e10,
        hbm_bw=20e9, vmem_bytes=32 * 2 ** 20, hbm_bytes=8 * 2 ** 30,
        supports_pallas=False,
        # python + XLA-CPU launch per compiled call (measured order of
        # magnitude); dominates tiny ragged lanes, so the bucket planner
        # fuses aggressively on hosts
        dispatch_overhead=3e-4),
}


# ``device_kind`` as JAX reports it (lower-cased) -> preset name.  A chip
# that is not listed has no preset: pricing it with another chip's peaks
# would rank candidates on numbers nobody measured.
_TPU_KINDS = {"tpu v5 lite": "tpu_v5e", "tpu v5e": "tpu_v5e",
              "tpu v4": "tpu_v4"}


def probe_machine(device=None) -> MachineModel:
    """The preset of ``device`` (default ``jax.devices()[0]``).

    CPU devices get the cpu preset and TPUs whose ``device_kind`` is in
    ``_TPU_KINDS`` their chip's preset.  Anything else raises ValueError
    (pass ``machine=`` explicitly), and so does a backend that fails to
    initialize — neither is mistaken for a host CPU.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return PRESETS["cpu"]
    name = (_TPU_KINDS.get(str(device.device_kind).lower())
            if device.platform == "tpu" else None)
    if name is None:
        raise ValueError(
            f"no machine preset for {device.platform} device kind "
            f"{device.device_kind!r}; pass machine= explicitly")
    return PRESETS[name]


def device_kind_tag(device=None) -> str:
    """Stable string identifying the device kind (autotune cache key)."""
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            return "unknown"
    kind = getattr(device, "device_kind", "") or getattr(device, "platform",
                                                         "unknown")
    return str(kind).replace(" ", "_")


# ---------------------------------------------------------------------------
# Cost breakdown
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Cost:
    """Per-processor resource counts for one variant (paper units: words)."""
    words: float          # interconnect words moved (the paper's W)
    messages: float       # latency hops on the critical path
    flops: float          # local FLOPs
    hbm_words: float      # local HBM words touched (reads + writes)

    def seconds(self, machine: MachineModel, itemsize: int = 4) -> float:
        """Execution estimate: local work overlaps compute with memory
        (max of terms), but the shard_map programs serialize collectives
        with the local GEMM, so network time and latency are added — which
        also keeps variants with identical FLOPs (e.g. redist/no_redist)
        ranked by their word counts rather than by latency noise."""
        t_net = self.words * itemsize / machine.byte_bw
        t_flop = self.flops / machine.flop_rate
        t_mem = self.hbm_words * itemsize / machine.hbm_bw
        return max(t_flop, t_mem) + t_net + self.messages * machine.alpha

    def bottleneck(self, machine: MachineModel, itemsize: int = 4) -> str:
        terms = {
            "network": self.words * itemsize / machine.byte_bw,
            "compute": self.flops / machine.flop_rate,
            "memory": self.hbm_words * itemsize / machine.hbm_bw,
        }
        return max(terms, key=terms.get)


# ---------------------------------------------------------------------------
# Variant costs — sketch  B = A·Omega  (n1 x n2  @  n2 x r)
# ---------------------------------------------------------------------------

def alg1_cost(n1: int, n2: int, r: int,
              grid: Tuple[int, int, int],
              backend: str = "jnp") -> Cost:
    """Alg. 1 on (p1, p2, p3): words is the paper's closed form exactly.

    ``backend`` prices the *local* GEMM body (kernels/local.py): the jnp
    backend materializes the per-shard Omega block in HBM
    (n2·r/(p2·p3) words); the pallas backend generates it in VMEM, so
    that term vanishes — the HBM-roofline analogue of the paper's
    zero-communication claim.  Network words are identical by construction.
    """
    p1, p2, p3 = grid
    P = p1 * p2 * p3
    words = alg1_bandwidth_words(n1, n2, r, p1, p2, p3)
    # per device: read the gathered A panel + regenerated Omega block
    # (write+read through VMEM; zero for the fused backend), write the
    # B shard.
    omega_hbm = 0.0 if backend == "pallas" else n2 * r / (p2 * p3)
    hbm = (n1 * n2 / (p1 * p2) + omega_hbm + n1 * r / P)
    return Cost(words=words, messages=alg1_latency_hops(p2, p3),
                flops=2.0 * n1 * n2 * r / P, hbm_words=hbm)


def alg1_communicating_cost(n1: int, n2: int, r: int,
                            grid: Tuple[int, int, int]) -> Cost:
    """The Fig.-3 anti-pattern: Omega all-gathered instead of regenerated.
    Never chosen; kept in candidate lists so reports can show the margin."""
    base = alg1_cost(n1, n2, r, grid)
    P = grid[0] * grid[1] * grid[2]
    omega_words = (1.0 - 1.0 / P) * n2 * r  # receive the rest of Omega
    return dataclasses.replace(
        base, words=base.words + omega_words,
        messages=base.messages + math.log2(max(P, 1)))


def local_cost(n1: int, n2: int, r: int) -> Cost:
    """Single-device GEMM with Omega materialized in HBM."""
    return Cost(words=0.0, messages=0.0, flops=2.0 * n1 * n2 * r,
                hbm_words=float(n1 * n2 + n2 * r + n1 * r))


def hbm_roofline_words(m: int, k: int, n: int, backend: str,
                       accumulate: bool = False) -> float:
    """Local HBM words of one (m×k)·(k×n) sketch GEMM per backend.

    The words-moved table behind the backend dispatch (see
    docs/COMMUNICATION_MODEL.md "HBM roofline"): jnp streams the operand,
    the materialized Omega block, and the output; pallas generates Omega in
    VMEM so the k·n term vanishes.  ``accumulate=True`` prices ``out += ``
    consumers (the streaming updates): jnp's separate delta + add costs
    4·m·n words (delta write/read + out read/write), the fused kernel's
    aliased accumulator 2·m·n (out read at k==0, write at the flush).
    """
    omega = 0.0 if backend == "pallas" else float(k * n)
    out = (2.0 if backend == "pallas" else 4.0) * m * n if accumulate \
        else float(m * n)
    return m * k + omega + out


def pallas_fused_cost(n1: int, n2: int, r: int) -> Cost:
    """Fused kernel: the n2·r Omega stream never touches HBM (§6.3 applied
    to the memory hierarchy — see kernels/sketch_matmul.py)."""
    return Cost(words=0.0, messages=0.0, flops=2.0 * n1 * n2 * r,
                hbm_words=float(n1 * n2 + n1 * r))


# ---------------------------------------------------------------------------
# Variant costs — Nyström  (B = A·Omega ; C = Omega^T·B)
# ---------------------------------------------------------------------------

def redistribute_words(n: int, r: int, p: Tuple[int, int, int],
                       q: Tuple[int, int, int]) -> float:
    """Per-processor words of the §5.2 ``Redistribute`` of B between the
    stage-1 and stage-2 grids: zero when q == p (B is already in place),
    else the all-to-all re-layout bound nr/P — every processor holds nr/P
    words of B and in the worst case all of them change owner.  This is
    exactly the ``p != q`` term inside ``alg2_bandwidth_words``, broken out
    so plans and reports can show the redistribution separately."""
    if tuple(p) == tuple(q):
        return 0.0
    P = p[0] * p[1] * p[2]
    return n * r / P


def fused_redistribute_words(n: int, r: int, p: Tuple[int, int, int],
                             q: Tuple[int, int, int]) -> float:
    """Per-processor words of the §5.2 ``Redistribute`` when it is expressed
    IN-PROGRAM (``nystrom_two_grid_fused``): the min-cut between B's
    stage-1 layout P((p1, p2), p3) and its stage-2 layout P(q1, (q3, q2))
    over the shared device order.  Each device keeps the overlap between
    its two shards and only receives the rest, so this is at most the
    cross-mesh bound nr/P (``redistribute_words``) and strictly below it
    whenever any device's shards intersect — e.g. the regime-1 pair
    p=(P,1,1), q=(1,1,P) moves nr/P - nr/P^2 words.  Computed exactly as
    the max over devices of (q-shard words) - (overlap words)."""
    p1, p2, p3 = p
    q1, q2, q3 = q
    P = p1 * p2 * p3
    pr, pc = n / (p1 * p2), r / p3            # p-layout shard extents
    qr, qc = n / q1, r / (q2 * q3)            # q-layout shard extents
    worst = 0.0
    for d in range(P):
        rb, cb = divmod(d, p3)                # p-coords of device d
        iq, rem = divmod(d, q2 * q3)          # q-coords of device d
        jq, kq = divmod(rem, q3)
        col_blk = kq * q2 + jq                # cols sharded (q3, q2)-major
        ov_r = max(0.0, min(rb * pr + pr, iq * qr + qr)
                   - max(rb * pr, iq * qr))
        ov_c = max(0.0, min(cb * pc + pc, col_blk * qc + qc)
                   - max(cb * pc, col_blk * qc))
        worst = max(worst, qr * qc - ov_r * ov_c)
    return worst


def alg2_fused_cost(n: int, r: int, p: Tuple[int, int, int],
                    q: Tuple[int, int, int], backend: str = "jnp") -> Cost:
    """Alg. 2 compiled as ONE program (``nystrom_two_grid_fused``): same
    stage collectives as :func:`alg2_cost`, but the cross-mesh nr/P
    Redistribute term is replaced by the in-program min-cut resharding
    (:func:`fused_redistribute_words`) and its log2(P) host-mediated hops
    by one in-program collective.  Words never drop below the Theorem 3
    floor — the stage All-Gather / Reduce-Scatter terms are untouched and
    the min-cut is the traffic a REAL schedule moves (pinned by
    tests/test_two_grid_fused.py across swept (n, r, P))."""
    _, p2, p3 = p
    base = alg2_cost(n, r, p, q, backend=backend)
    cross = redistribute_words(n, r, p, q)
    fused = fused_redistribute_words(n, r, p, q)
    msgs = alg1_latency_hops(p2, p3) + math.log2(max(p[0], 1))
    if fused > 0.0:
        msgs += 1.0                   # one in-program resharding collective
    return dataclasses.replace(base, words=base.words - cross + fused,
                               messages=msgs)


def alg2_cost(n: int, r: int, p: Tuple[int, int, int],
              q: Tuple[int, int, int], backend: str = "jnp") -> Cost:
    """Alg. 2 on grids (p, q): words is ``alg2_bandwidth_words`` exactly
    (which already includes ``redistribute_words`` when p != q), so a
    shard_map winner's predicted words stay equal to the paper's closed
    form and never fall below the Theorem 3 bound.

    ``backend`` prices the local bodies of both stages: pallas drops the
    Omega regeneration HBM streams (stage 1's A·Omega block and stage 2's
    Omega^T·B block) entirely — they live only in VMEM.
    """
    p1, p2, p3 = p
    P = p1 * p2 * p3
    words = alg2_bandwidth_words(n, r, p, q)
    omega_hbm = 0.0 if backend == "pallas" else 2.0 * n * r / P
    hbm = (n * n / (p1 * p2)          # A panel
           + omega_hbm                # Omega regen (stage 1 + stage 2)
           + 2.0 * n * r / P          # B write + B re-read
           + r * r / P)               # C shard
    msgs = alg1_latency_hops(p2, p3) + math.log2(max(p1, 1))
    if tuple(p) != tuple(q):
        msgs += math.log2(max(P, 1))  # the all-to-all redistribution
    return Cost(words=words, messages=msgs,
                flops=(2.0 * n * n * r + 2.0 * n * r * r) / P, hbm_words=hbm)


def nystrom_local_cost(n: int, r: int, fused: bool = False) -> Cost:
    """Single-device Nyström pair; ``fused`` drops both Omega HBM streams."""
    omega_words = 0.0 if fused else 2.0 * n * r
    return Cost(words=0.0, messages=0.0,
                flops=2.0 * n * n * r + 2.0 * n * r * r,
                hbm_words=float(n * n + omega_words + 2 * n * r + r * r))


# ---------------------------------------------------------------------------
# Variant costs — streaming ingest (one row-slab update of k rows)
# ---------------------------------------------------------------------------

def stream_update_cost(k: int, n2: int, r: int, l: int,
                       grid: Tuple[int, int, int] = (1, 1, 1),
                       corange: bool = True,
                       backend: str = "jnp") -> Cost:
    """One ``update_rows`` step folding a (k, n2) slab.

    Local grid (1,1,1): zero network words.  Sharded: the slab (replicated
    over p1, column-sharded over (p2, p3)) pays one All-Gather over p3 and
    one All-Reduce of the dY partial over p2, plus nothing for W (replicated
    over p1, update fully local) — see stream/distributed.py:update_rows.

    HBM accounting per backend, priced for the row-slab ingest this plan
    actually executes (``update_rows``): the jnp body materializes the
    Omega block (n2·r/(p2·p3) words) and, when the co-range sketch is on,
    the Psi slab (k·l words) plus a W read-modify-write through a
    materialized delta (4·l·n2/(p2·p3) accumulate words).  The pallas
    body generates Omega/Psi in VMEM and fuses ``W += Psi·H`` into the
    kernel accumulator (``sketch_t_block(acc=w)``): zero Omega/Psi words
    and one W round trip (2·l·n2/(p2·p3)).  The traced-offset Y fold is
    backend-dispatched too (``kernels.local.fold_rows_block``): the jnp
    body round-trips dY plus the zero-padded frame (4·k·r/p3 accumulate
    words), the pallas body DMAs each Y block's slab window (no padded
    frame) and aliases the Y shard in-place (2·k·r/p3).
    """
    p1, p2, p3 = grid
    words = 0.0
    msgs = 0.0
    if p3 > 1:
        words += (1.0 - 1.0 / p3) * k * n2 / p2
        msgs += math.log2(p3)
    if p2 > 1:
        words += 2.0 * (1.0 - 1.0 / p2) * k * r / p3   # all-reduce of dY
        msgs += 2.0 * math.log2(p2)
    flops = 2.0 * k * n2 * r / (p2 * p3)
    fused = backend == "pallas"
    omega_hbm = 0.0 if fused else n2 * r / (p2 * p3)
    acc_hbm = (2.0 if fused else 4.0) * k * r / p3     # fused Y fold
    hbm = k * n2 / (p2 * p3) + omega_hbm + acc_hbm
    if corange:
        flops += 2.0 * k * n2 * l / (p2 * p3)
        psi_hbm = 0.0 if fused else k * l
        hbm += psi_hbm + (2.0 if fused else 4.0) * l * n2 / (p2 * p3)
    return Cost(words=words, messages=msgs, flops=flops, hbm_words=hbm)


#: Flop-rate penalty of scalar scatter-adds relative to the dense GEMM's
#: vectorized FMAs (no tensor cores, gather/scatter addressing, bank
#: conflicts).  One knob, deliberately pessimistic: the planner should
#: pick sparse only when the O(nnz) arithmetic saving is decisive, not on
#: a coin flip the hardware would lose.
SPARSE_SCATTER_PENALTY = 8.0


def sparse_payload_words(nnz: int) -> float:
    """Wire/storage words of a COO payload: one index + one value per
    stored entry — what a sparse row slab costs to ship instead of its
    dense (k, n2) frame (see docs/COMMUNICATION_MODEL.md)."""
    return 2.0 * float(nnz)


def _sparse_participation(n2: int, r: int, kind: str) -> float:
    """Fraction of input columns a sparse Omega actually touches:
    CountSketch hits every row of Omega; coordinated row sampling keeps a
    row with probability r/n2 (seed-coordinated, so every party agrees on
    the subset without communicating it)."""
    return min(1.0, r / max(n2, 1)) if kind == "rowsample" else 1.0


def sparse_sketch_cost(n1: int, n2: int, r: int, nnz: float,
                       grid: Tuple[int, int, int] = (1, 1, 1),
                       kind: str = "countsketch") -> Cost:
    """B = A·Omega with a SPARSE Omega family (CountSketch / coordinated
    row sampling) on a stored-sparse A with ``nnz`` nonzeros.

    Arithmetic is O(nnz): each stored entry contributes one scatter-add
    into its bucket column (times ``SPARSE_SCATTER_PENALTY`` against the
    dense GEMM's vectorized flop rate).  Communication replaces the dense
    A-panel All-Gather of Alg. 1 with a COO panel — (indices + values) =
    ``2·nnz_eff/(p1·p2)`` words over the p3 axis, where ``nnz_eff`` drops
    to ``nnz·r/n2`` for rowsample because senders filter by the
    seed-coordinated membership before shipping.  The Reduce-Scatter of
    the B partial over p2 is the dense Alg.-1 term unchanged: B is dense
    whatever Omega was.
    """
    p1, p2, p3 = grid
    P = p1 * p2 * p3
    nnz_eff = float(nnz) * _sparse_participation(n2, r, kind)
    words = 0.0
    msgs = 0.0
    if p3 > 1:
        words += (1.0 - 1.0 / p3) * sparse_payload_words(nnz_eff) / (p1 * p2)
        msgs += math.log2(p3)
    if p2 > 1:
        words += (1.0 - 1.0 / p2) * n1 * r / (p1 * p3)
        msgs += math.log2(p2)
    flops = 2.0 * nnz_eff * SPARSE_SCATTER_PENALTY / P
    # read the COO panel; one accumulator read-modify-write per scatter
    # (random buckets — no cache reuse, unlike the GEMM's streaming
    # access); write the (dense) B shard.  The sparse Omega itself is
    # generated from counters — never materialized, zero HBM words.
    hbm = (sparse_payload_words(nnz_eff) + 2.0 * nnz_eff + n1 * r) / P
    return Cost(words=words, messages=msgs, flops=flops, hbm_words=hbm)


def sparse_stream_update_cost(k: int, n2: int, r: int, l: int, nnz: float,
                              grid: Tuple[int, int, int] = (1, 1, 1),
                              corange: bool = True,
                              kind: str = "countsketch") -> Cost:
    """One ``update_rows_sparse`` step folding a (k, n2) COO slab with
    ``nnz`` stored entries (``stream/state.py:_local_sparse_update``).

    Local grid: zero network words — the interesting number is the
    *payload* (priced by :func:`sparse_payload_words` at the service
    ledger site) and the O(nnz) fold.  Sharded grids ship the COO panel
    over p3 instead of the dense slab — same substitution as
    :func:`sparse_sketch_cost`; the dY All-Reduce over p2 is dense.

    A sparse KIND folds one scatter-add per entry into Y (and one into W
    when corange); a dense kind gathers an r-row of the regenerated Omega
    per entry (nnz·r flops) and an l-row of Psi likewise.
    """
    p1, p2, p3 = grid
    nnz_eff = float(nnz) * _sparse_participation(n2, r, kind)
    sparse_om = kind in ("countsketch", "rowsample")
    words = 0.0
    msgs = 0.0
    if p3 > 1:
        words += (1.0 - 1.0 / p3) * sparse_payload_words(nnz_eff) / p2
        msgs += math.log2(p3)
    if p2 > 1:
        words += 2.0 * (1.0 - 1.0 / p2) * k * r / p3   # all-reduce of dY
        msgs += 2.0 * math.log2(p2)
    per_entry = 1.0 if sparse_om else float(r)
    flops = 2.0 * nnz_eff * per_entry * SPARSE_SCATTER_PENALTY / (p2 * p3)
    # COO read + one dY read-modify-write per scatter + the Y fold
    hbm = ((sparse_payload_words(nnz_eff) + 2.0 * nnz_eff) / (p2 * p3)
           + 4.0 * k * r / p3)
    if corange:
        flops += (2.0 * nnz_eff * (1.0 if sparse_om else float(l))
                  * SPARSE_SCATTER_PENALTY / (p2 * p3))
        hbm += (2.0 * nnz_eff + 2.0 * l * n2) / (p2 * p3)
    return Cost(words=words, messages=msgs, flops=flops, hbm_words=hbm)


def stream_reshard_words(n1: int, r: int, p: Tuple[int, int, int],
                         q: Tuple[int, int, int], *, l: int = 0,
                         n2: int = 0, corange: bool = False) -> float:
    """Per-processor words of the one-hop elastic reshard
    (``stream/elastic.py reshard_stream``): re-laying a live accumulator's
    (Y, W) from grid ``p`` onto grid ``q`` in a single resharding hop.

    Exact per-device min-cut over the shared linear device order, the same
    construction as :func:`fused_redistribute_words`: each device keeps the
    overlap between its old and new shards and only receives the rest, so
    the cost is  max over receiving devices of (new-shard words) -
    (overlap words).  Layouts follow stream/distributed.py: Y (n1 x r) is
    P((p1, p2), p3) — device d holds row block d // p3 of p1·p2 and column
    block d % p3 of p3 — and W (l x n2), present when ``corange``, is
    P(None, (p2, p3)) — replicated over p1, column block d % (p2·p3).

    When device counts differ (grow / shrink) the device order is
    prefix-shared (``make_grid_mesh`` takes ``devices[:P]``): the first
    min(P, Q) devices keep their overlap, fresh devices receive their full
    shards, and shed devices only send.  Identical effective layouts —
    e.g. (8,1,1) -> (4,2,1), whose Y row blocks coincide — cost zero: the
    hop is a relabeling, and the compiled relayout emits no collective.

    This min-cut is the hop's *floor* (the ledger's ``lower_bound_words``
    for the ``stream.reshard`` site); what a compiled relayout actually
    moves is :func:`stream_reshard_traffic_words` — XLA round-trips full
    shards, achieving the floor only where the floor is 0 or full-shard.
    """
    p1, p2, p3 = p
    q1, q2, q3 = q
    P, Q = p1 * p2 * p3, q1 * q2 * q3
    pr, pc = n1 / (p1 * p2), r / p3          # old Y shard extents
    qr, qc = n1 / (q1 * q2), r / q3          # new Y shard extents
    worst = 0.0
    for d in range(Q):
        nrb, ncb = divmod(d, q3)
        need = qr * qc
        if d < P:
            rb, cb = divmod(d, p3)
            ov_r = max(0.0, min(rb * pr + pr, nrb * qr + qr)
                       - max(rb * pr, nrb * qr))
            ov_c = max(0.0, min(cb * pc + pc, ncb * qc + qc)
                       - max(cb * pc, ncb * qc))
            need -= ov_r * ov_c
        if corange:
            wp, wq = n2 / (p2 * p3), n2 / (q2 * q3)   # W col extents
            nwb = d % (q2 * q3)
            w_need = l * wq
            if d < P:
                wb = d % (p2 * p3)
                ov_w = max(0.0, min(wb * wp + wp, nwb * wq + wq)
                           - max(wb * wp, nwb * wq))
                w_need -= l * ov_w
            need += w_need
        worst = max(worst, need)
    return worst


def stream_reshard_traffic_words(n1: int, r: int, p: Tuple[int, int, int],
                                 q: Tuple[int, int, int], *, l: int = 0,
                                 n2: int = 0,
                                 corange: bool = False) -> float:
    """Per-processor words the COMPILED one-hop relayout actually moves —
    the ledger's *predicted* words for the ``stream.reshard`` site, next
    to the :func:`stream_reshard_words` min-cut floor.

    XLA's SPMD partitioner implements a layout change as shard-sized
    collective traffic — full shards, not the overlap-aware min-cut — and
    the exact count follows from which axes re-split (calibrated against
    the compiled HLO of every 8-device grid pair, exhaustively pinned by
    tests/test_fault_tolerance.py):

    * **Y** (n1 x r, P((p1,p2), p3); device d -> row block d // p3, col
      block d % p3).  Maps coincide (block counts equal, same device
      count) -> the hop compiles away: 0 words.  Re-splitting an
      already-split column axis (p3 > 1 AND q3 > 1 AND p3 != q3) forces
      TWO full-shard hops — an all-to-all re-splitting the rows plus a
      collective-permute re-routing the columns — so the device pays 2x
      its new shard.  Every other layout change folds into a single
      all-to-all: 1x the new shard.
    * **W** (l x n2, P(None, (p2,p3)); device d -> col block d % (p2·p3),
      replicated over the rest).  Same block count -> 0.  Splitting OUT
      of replicated (p2·p3 == 1) onto the same or fewer devices is a
      local slice of the replica: 0 words (a grown device set still
      ships the new shard to each fresh device).  COARSENING the split
      (q2·q3 < p2·p3) is all-gather traffic counted at its per-device
      operand — the OLD shard: l·n2/(p2·p3) words into replicated, twice
      that (gather + permute hop) when the coarser layout is still split.
      Re-splitting FINER moves 1x the new W shard.
    """
    p1, p2, p3 = p
    q1, q2, q3 = q
    P, Q = p1 * p2 * p3, q1 * q2 * q3
    words = 0.0
    # Y P((p1,p2), p3): the maps coincide iff the block counts do
    same_y = (p1 * p2 == q1 * q2 and p3 == q3 and P == Q)
    if not same_y:
        hops = 2.0 if (p3 > 1 and q3 > 1 and p3 != q3) else 1.0
        words += hops * n1 / (q1 * q2) * (r / q3)  # full new Y shard(s)
    if corange:
        # W P(None, (p2,p3)): device d -> col block d % (p2·p3)
        bp, bq = p2 * p3, q2 * q3
        if bp == bq and P == Q:
            pass                                   # same map: no traffic
        elif bp == 1 and Q <= P:
            pass                                   # slice out of replica
        elif bq < bp:
            # all-gather counted at its operand (the OLD shard); a
            # coarser-but-still-split target adds a permute hop
            words += (2.0 if bq > 1 else 1.0) * l * n2 / bp
        else:
            words += l * n2 / bq                   # full new W shard
    return words


# ---------------------------------------------------------------------------
# Variant costs — data-parallel gradient exchange (parallel/grad_compress.py)
# ---------------------------------------------------------------------------

def grad_allreduce_cost(m: int, n: int, world: int) -> Cost:
    """Raw data-parallel exchange of one (m, n) gradient leaf: a single
    all-reduce (``pmean`` over the data axis) moving the full operand.

    Words follow the repo's HLO-audit convention (``roofline/hlo.py``
    counts an all-reduce at its per-device operand size, the same unit
    the Theorem 2 bounds and the comm ledger use): ``m·n`` words per
    processor, ``log2(P)`` latency hops.  ``world <= 1`` is free — a
    pmean over a singleton axis lowers to no collective at all.
    """
    if world <= 1:
        return Cost(words=0.0, messages=0.0, flops=0.0,
                    hbm_words=2.0 * m * n)
    return Cost(words=float(m * n), messages=math.log2(world),
                flops=float(m * n),            # the reduction adds
                hbm_words=2.0 * m * n)         # leaf read + reduced write


def grad_compress_cost(m: int, n: int, r: int, world: int,
                       backend: str = "jnp") -> Cost:
    """Sketched exchange of one (m, n) gradient leaf at rank ``r``
    (``parallel/grad_compress.py``): the Theorem-2 regime-1 trade applied
    to the DP all-reduce — Omega is regenerated from the counter-based
    seed on every worker (zero words, the paper's central claim), so only
    the two data-dependent factors move:

        P  = pmean((G+E)·Omega)      m·r words
        Qᵀ = pmean(P̂ᵀ·(G+E))         r·n words

    for ``r·(m+n)`` total vs the raw ``m·n`` — the planner's crossover is
    ``r < m·n/(m+n)`` (docs/TRAINING.md works it out).  Local work added:
    four rank-r GEMMs (the two sketch GEMMs above plus the decompression
    ``P̂·Qᵀ`` and the error-feedback update ``E' = M − P̂·Q_locᵀ``),
    ``2·m·r²`` for the thin QR of P, and the ``M = G+E`` add.

    ``backend`` prices the local bodies through ``kernels/local.py``: the
    pallas sketch kernel generates Omega in VMEM (the ``n·r`` HBM stream
    vanishes) and the fused dense kernel (``gemm_block``) aliases the
    error-feedback accumulator in-place, halving its ``4·m·n`` jnp
    read-modify-write to ``2·m·n`` — identical network words either way.
    """
    r = min(r, m, n)
    words = float(r * (m + n)) if world > 1 else 0.0
    msgs = 2.0 * math.log2(world) if world > 1 else 0.0
    flops = 8.0 * m * n * r + 2.0 * m * r * r + float(m * n)
    # M = G+E materialization: read both, write M.
    hbm = 3.0 * m * n
    # sketch GEMM M·Omega (hbm_roofline_words: pallas drops the n·r
    # Omega stream), + QR of the m×r pmean result (round trip).
    hbm += hbm_roofline_words(m, n, r, backend) + 2.0 * m * r
    # dense P̂ᵀ·M: both operands resident in HBM on either backend.
    hbm += m * r + float(m * n) + r * n
    # decompression P̂·Qᵀ writes the g_hat leaf.
    hbm += m * r + r * n + float(m * n)
    # error-feedback update E' = M − P̂·Q_locᵀ: jnp materializes the
    # delta then read-modify-writes (4·m·n); the fused kernel aliases
    # the accumulator (2·m·n) — same halving as the streaming W update.
    acc = (2.0 if backend == "pallas" else 4.0) * m * n
    hbm += m * r + r * n + acc
    return Cost(words=words, messages=msgs, flops=flops, hbm_words=hbm)


# ---------------------------------------------------------------------------
# Ragged-ingest bucket planning (padded-lane waste vs dispatch amortization)
# ---------------------------------------------------------------------------

def ragged_bucket_cost(ks, kb: int, n2: int, r: int, l: int,
                       corange: bool = True, backend: str = "jnp",
                       machine: MachineModel = None,
                       itemsize: int = 4) -> float:
    """Predicted seconds of ONE fused bucket dispatch ingesting ``len(ks)``
    ragged lanes padded to height ``kb`` (each ``k in ks`` must be <= kb).

    One host dispatch, then the vmapped lanes execute back to back on the
    device, each paying the FULL padded-slab work — padded rows are masked,
    not skipped, so their FLOPs and HBM traffic are real.  That waste is
    what the dispatch saving has to beat; :func:`choose_bucket_edges` runs
    the comparison exactly.
    """
    machine = machine or probe_machine()
    lane = stream_update_cost(kb, n2, r, l, corange=corange, backend=backend)
    return (machine.dispatch_overhead
            + len(list(ks)) * lane.seconds(machine, itemsize))


def choose_bucket_edges(ks, n2: int, r: int, l: int = None,
                        corange: bool = True, backend: str = "jnp",
                        machine: MachineModel = None,
                        itemsize: int = 4) -> list:
    """Optimal shape-bucket boundaries for a ragged ingest workload.

    ``ks`` is the observed distribution of lane heights (one entry per
    update).  Returns ascending bucket tops (for
    ``SketchService.update_ragged(bucket_edges=...)`` /
    ``IngestQueue(bucket_edges=...)``); every lane is padded up to the
    smallest edge >= its height.

    Exact DP over the sorted unique heights (buckets are contiguous height
    ranges in an optimal solution — padding a lane past the next-larger
    occupied height is never cheaper than stopping there), minimizing

        sum over buckets [ dispatch_overhead
                           + count(bucket) * lane_seconds(bucket top) ].

    Limits (pinned by tests/test_service_scale.py): zero dispatch overhead
    degenerates to one bucket per distinct height (no padding is ever
    free); a dispatch cost dominating the per-lane work collapses to a
    single bucket at max(ks).

    Height 1, when present, is always its own bucket: ``snap_bucket``
    refuses to pad single-row slabs (XLA's M=1 gemv reduction order
    differs from the packed gemm loop, which would break the bitwise
    lane-vs-solo contract), so the DP plans the remaining heights around
    a mandatory [1] edge.
    """
    machine = machine or probe_machine()
    if l is None:
        l = 2 * r + 1
    ks = sorted(int(k) for k in ks)
    if not ks:
        return []
    if ks[0] <= 1:
        rest = [k for k in ks if k > 1]
        return [1] + choose_bucket_edges(
            rest, n2, r, l, corange=corange, backend=backend,
            machine=machine, itemsize=itemsize)
    uniq = sorted(set(ks))
    counts = [ks.count(u) for u in uniq]
    lane_s = [stream_update_cost(u, n2, r, l, corange=corange,
                                 backend=backend).seconds(machine, itemsize)
              for u in uniq]
    m = len(uniq)
    best = [0.0] * (m + 1)          # best[j]: heights uniq[:j] bucketed
    cut = [0] * (m + 1)
    for j in range(1, m + 1):
        best[j] = math.inf
        tail = 0
        for i in range(j, 0, -1):   # bucket = uniq[i-1 .. j-1], top uniq[j-1]
            tail += counts[i - 1]
            c = best[i - 1] + machine.dispatch_overhead + tail * lane_s[j - 1]
            if c < best[j]:
                best[j], cut[j] = c, i - 1
    edges = []
    j = m
    while j > 0:
        edges.append(uniq[j - 1])
        j = cut[j]
    return edges[::-1]
