"""Per-``Hardware`` kernel tile autotuner (the hetero "kernel speed pass").

Whale shapes *work* per hardware tier (its load balancers hand a P100 group
fewer layers/smaller batches than a V100 group); this module applies the
same idea one level down, to *tile geometry*: the same Pallas kernel should
tile differently on a part with 4 MiB of fast on-chip memory and a 10:1
compute/bandwidth ratio than on one with 16 MiB and 130:1.

The choice is analytic (the repo's meta-driven idiom — nothing is run):

- **cap** — roofline arithmetic-intensity target.  A flash tile of side
  ``t`` reuses each loaded K/V byte ~``t`` times, so to keep the MXU fed
  we want ``t ≳ flops_per_hbm_byte``; we aim at 4× the balance point and
  clamp to [64, 512] (the MXU is 128×128 — below 64 the systolic array
  starves, above 512 latency/VMEM pressure dominate).  Computed caps:
  TPU-v5e 512, T4 512, V100 256, P100 64 — so a V100 group and a P100
  group in the same job really do tile differently.
- **fit** — the largest power-of-two tile ≤ cap whose VMEM working set
  (modelled per kernel family below) fits half the part's ``vmem_bytes``
  (half: double-buffered async copies need the other half).

Both criteria are monotone in (``vmem_bytes``, ``flops_per_hbm_byte``), so
a strictly smaller part never gets a larger tile — property-tested in
tests/test_autotune.py.  One deliberate exception: the xent *vocab* tile
shares its budget with the token tile, so when a lower compute ratio
shrinks ``bt`` the freed bytes may widen ``bv`` — the joint working set
still shrinks with the part.

Sequence-fitting: chosen tiles are powers of two, and the model layer pads
sequences/vocab to multiples of the tile anyway; when an actual length is
known, :func:`fit_block` snaps a tile down to the largest divisor.
"""
from __future__ import annotations

import dataclasses

from repro.core.cost_model import Hardware

# today's fixed constants (pre-autotune defaults) — unknown hardware and
# ``autotune(None)`` fall back to exactly these.
DEFAULT_TILES = None  # set below, after KernelTiles is defined

_MIN_TILE, _MAX_TILE = 64, 512


@dataclasses.dataclass(frozen=True)
class KernelTiles:
    """One device group's tile geometry for every fused-kernel family."""
    block_q: int = 128          # flash attention q-tile rows
    block_k: int = 128          # flash attention kv-tile rows
    xent_block_t: int = 128     # fused-xent token tile
    xent_block_v: int = 512     # fused-xent vocab tile
    ssd_chunk: int = 128        # SSD intra-chunk length
    page_size: int = 64         # paged-KV decode page rows (serving)

    def shrink_to(self, seq: int | None = None, vocab: int | None = None
                  ) -> "KernelTiles":
        """Snap tiles down to divisors of actual (padded) lengths."""
        return dataclasses.replace(
            self,
            block_q=fit_block(seq, self.block_q) if seq else self.block_q,
            block_k=fit_block(seq, self.block_k) if seq else self.block_k,
            xent_block_v=(fit_block(vocab, self.xent_block_v) if vocab
                          else self.xent_block_v),
            ssd_chunk=fit_block(seq, self.ssd_chunk) if seq else self.ssd_chunk,
        )


DEFAULT_TILES = KernelTiles()


def fit_block(n: int, target: int, align: int = 1) -> int:
    """Largest divisor of ``n`` that is ≤ ``target`` and a multiple of
    ``align`` (≥ 1 always exists for ``align=1``; a TPU lane dim passes
    ``align=128``)."""
    if n <= 0:
        raise ValueError(f"length must be positive, got {n}")
    t = min(target, n) // align * align
    while t >= align:
        if n % t == 0:
            return t
        t -= align
    raise ValueError(f"no multiple of {align} ≤ {target} divides {n}")


def _pow2_floor(x: float) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def _pow2_ceil(x: float) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _cap(hw: Hardware) -> int:
    """Roofline tile-side target for this part, clamped to [64, 512]."""
    return max(_MIN_TILE, min(_MAX_TILE,
                              _pow2_ceil(4 * hw.flops_per_hbm_byte)))


def _largest_fitting(budget: float, cap: int, bytes_at) -> int:
    """Largest power-of-two tile ≤ cap with bytes_at(tile) ≤ budget."""
    t = _pow2_floor(cap)
    while t > 8 and bytes_at(t) > budget:
        t //= 2
    return t


def autotune(hw: Hardware | None, *, head_dim: int = 128, group: int = 1,
             d_model: int | None = None, vocab: int | None = None,
             seq: int | None = None) -> KernelTiles:
    """Pick tile sizes for one hardware part.

    ``hw=None`` (unknown/absent hardware table) returns today's defaults.
    ``seq``/``vocab``, when given, snap the result onto actual lengths.
    """
    if hw is None:
        return DEFAULT_TILES.shrink_to(seq=seq, vocab=vocab)

    cap = _cap(hw)
    budget = hw.vmem_bytes / 2          # other half: double buffering
    f32 = 4

    # flash: square-ish tile t×t; resident = q/do/acc rows (3·t·G·D) +
    # k/v tile (2·t·D) + score tile (t·G × t), all f32 in-kernel.
    D, G = head_dim, group
    bq = _largest_fitting(
        budget, cap,
        lambda t: f32 * (3 * t * G * D + 2 * t * D + t * G * t))
    tiles_bk = bq                       # symmetric tiles: one roofline knob

    # fused xent: resident = hidden tile (bt·E) + head tile (E·bv) +
    # logits tile (bt·bv).  Token tile tracks the flash tile; the vocab
    # tile is the wide axis (vocab ≫ seq) and gets up to 4× the cap.
    E = d_model or 8 * head_dim
    bt = bq
    bv = _largest_fitting(
        budget, min(4 * cap, 2048),
        lambda t: f32 * (bt * E + E * t + bt * t))

    # SSD: chunk c holds x/dt/B/C slabs (~4·c·D) + the c×c intra-chunk
    # attention-like matrix per head group.
    chunk = _largest_fitting(
        budget, cap, lambda t: f32 * (4 * t * D + t * t))

    # paged-KV decode page: one grid step holds a (page, D) k and v tile,
    # the (G, page) score strip and the (G, D) q/acc strips.  The page is
    # both the kernel tile AND the allocator granularity, so it is capped
    # at 256 — larger pages waste allocator granularity faster than they
    # buy arithmetic intensity (decode is bandwidth-bound regardless).
    page = _largest_fitting(
        budget, min(cap, 256),
        lambda t: f32 * (2 * t * D + G * t + 2 * G * D))

    return KernelTiles(block_q=bq, block_k=tiles_bk, xent_block_t=bt,
                       xent_block_v=bv, ssd_chunk=chunk, page_size=page
                       ).shrink_to(seq=seq, vocab=vocab)


def autotune_cluster(cluster, *, head_dim: int = 128, group: int = 1,
                     d_model: int | None = None, vocab: int | None = None,
                     seq: int | None = None) -> dict:
    """Tiles for every :class:`DeviceGroup` in a :class:`ClusterSpec`.

    Returns ``{group.name: KernelTiles}``.  In a mixed V100+P100 job each
    group tiles for its own part — the per-group model functions the
    hetero planner builds then carry different static block sizes.
    """
    return {g.name: autotune(g.hw, head_dim=head_dim, group=group,
                             d_model=d_model, vocab=vocab, seq=seq)
            for g in cluster.groups}
