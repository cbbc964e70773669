"""Public wrappers for the Pallas kernels, with backend dispatch.

Every wrapper follows the platform: the int8 boundary pair
(``quantize_int8`` / ``dequantize_int8``), the SSD scan and the grouped
matmul of the expert layer run the compiled Pallas kernels on TPU and the
pure-XLA references elsewhere (the SSD scan and the grouped matmul also
where their blocks cannot tile the input). Off the model path, the flash
and decode attention kernels are called directly from their modules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import int8_transfer as ik
from repro.kernels import ref
from repro.kernels import ssd_scan as sk
from repro.obs import device_scope

# ---------------------------------------------------------------------------
# The authoritative int8 wire-compression ratio.
#
# Every layer that reasons about compressed boundary bytes — Algorithm 1
# (core.splitter), the §4 cost model (core.cost_model), the simulated
# server's wire charge (cos.server) and the benchmarks — derives it from
# here, so the splitter's prediction and the server's accounting can
# never disagree about what a compressed split puts on the trunk.
# ---------------------------------------------------------------------------
WIRE_TILE = 128                 # quantization tile: one scale per 128 lanes
SCALE_DTYPE = jnp.float32       # per-tile scales ride the wire in f32


def compression_ratio(dtype=jnp.bfloat16, tile: int = WIRE_TILE) -> float:
    """Exact wire-byte ratio of int8(+per-tile scales) vs raw activations.

    ``(itemsize_q + scale_bytes / tile) / itemsize_act`` — for bf16
    activations with the default 128-lane tile that is
    ``(1 + 4/128) / 2 = 0.515625`` (NOT 0.25: the scales cost 4 bytes per
    tile, and bf16 is already half of f32). ``tile`` should be the
    effective tile after the kernels' ``gcd(d, tile)`` clamp when the
    feature width is narrower than 128."""
    if tile <= 0:
        raise ValueError(f"tile must be > 0, got {tile}")
    itemsize = jnp.dtype(dtype).itemsize
    q_bytes = jnp.dtype(jnp.int8).itemsize
    scale_bytes = jnp.dtype(SCALE_DTYPE).itemsize
    return (q_bytes + scale_bytes / tile) / itemsize


# The simulator's wire convention: boundary activations ship bf16 when
# uncompressed, int8 + per-128 f32 scales when compressed (== 0.515625).
INT8_WIRE_RATIO = compression_ratio(jnp.bfloat16, WIRE_TILE)


def on_tpu() -> bool:
    """Whether this process computes on a TPU (the compiled-kernel path)."""
    return jax.default_backend() == "tpu"


_ref_quantize = jax.jit(ref.quantize_int8, static_argnames=("tile",))
_ref_dequantize = jax.jit(ref.dequantize_int8, static_argnames=("dtype",))


def ssd_scan(x, dtA, dt, B_, C_, D, init_state=None, *, chunk: int = 256):
    """Chunked SSD scan with its D skip: y = SSD(x) + D x in x's dtype,
    (B, S, H, P), and the final state (B, H, N, P) f32; B and C are
    (B, S, G, N). The compiled
    Pallas pair on TPU, ``ref.ssd_chunked`` elsewhere, and on TPU too
    where an initial state is given or the blocks cannot tile the input
    (``ssd_scan.plan_blocks``)."""
    if (on_tpu() and init_state is None
            and sk.plan_blocks(x.shape, B_.shape[-1], chunk, groups=B_.shape[-2]) is not None):
        return sk.ssd_scan_pallas(x, dtA, dt, B_, C_, D, chunk=chunk)
    y, state = ref.ssd_chunked(x, dtA, dt, B_, C_, init_state, chunk)
    y = y + D[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), state


GMM_ROWS = 512              # rows of one grouped-matmul tile
GMM_LANES = 384            # target width of its K and N tiles


def _gmm_tile(dim: int) -> int:
    """A K or N tile of the grouped matmul: ``GMM_LANES`` where it divides
    ``dim``, else the largest multiple of 128 under it that does, else the
    whole dim (Mosaic's blocks are multiples of 128 lanes or whole)."""
    for t in range(min(GMM_LANES, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def gmm_tiling(m: int, k: int, n: int):
    """(rows, K, N) tile of megablox's grouped matmul for an (m, k) by
    (k, n) problem; each matmul of its backward asks again."""
    return min(GMM_ROWS, m), _gmm_tile(k), _gmm_tile(n)


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """Rows of ``x`` (M, K), sorted by group, times their group's matrix of
    ``w`` (G, K, N): group g owns the ``group_sizes[g]`` rows after the
    groups before it, and rows past all groups come out undefined. On TPU
    (or ``interpret``) the Pallas grouped matmul that JAX ships
    (megablox: group offsets prefetched as scalars, only the row tiles
    that hold a group's rows visited, forward and backward), where the
    row tiles divide M; elsewhere XLA's ``ragged_dot``. Not XLA's on TPU:
    its TPU rewrite renames the op and drops the named scopes around it."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m = x.shape[0]
    if (on_tpu() or interpret) and m % gmm_tiling(*x.shape, w.shape[-1])[0] == 0:
        return megablox.gmm(x, w, group_sizes, x.dtype, gmm_tiling, None, None, False,
                            interpret)
    return jax.lax.ragged_dot(x, w, group_sizes)


def quantize_int8(x, tile: int = WIRE_TILE):
    """Per-tile int8 quantization of the split boundary: the compiled
    Pallas kernel on TPU (feature width a multiple of 128), the XLA
    reference elsewhere. Both carry the ``hapi.quantize`` scope."""
    with device_scope("hapi.quantize"):
        if on_tpu():
            return ik.quantize_int8_pallas(x, tile=tile)
        return _ref_quantize(x, tile=tile)


def dequantize_int8(q, scales, dtype=jnp.bfloat16):
    with device_scope("hapi.dequantize"):
        if on_tpu():
            return ik.dequantize_int8_pallas(q, scales, dtype=dtype)
        return _ref_dequantize(q, scales, dtype=dtype)
