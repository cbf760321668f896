"""Pallas TPU kernel for the shape-hash fold (the matcher's VPU core).

The shape-directed matcher (ops/shapes.py, replacing the reference's
per-message trie walk, emqx_trie.erl:208-266) spends its compute in a
per-level hash fold over [batch, shapes] lanes followed by two-choice home
bucket derivation and shape-compatibility masking. This kernel fuses the
whole L-level fold, the home computation, and the compatibility mask into
ONE VMEM-resident Pallas program (grid over batch blocks), so the level
loop never materializes intermediates in HBM and the mask/index outputs
come out in a single pass. The two bucket-row gathers stay in XLA (Mosaic
has no large-table vector gather; the gather is HBM-bound either way).

Layout (round-3 rework): the round-2 kernel tiled blocks as
[batch, shapes] — with the bench's single shape that is a 1-wide LANE
dimension, which Mosaic pads to 128 lanes, i.e. 127/128 of every VPU op
wasted (measured: pallas 8.4M/s vs XLA 9.3M/s, the round-2 rent problem).
Here the batch block spans the full native tile — [SB=8 sublanes,
BL=512 lanes] — and the (static, <= 32) shape axis is an unrolled python
loop reading its per-shape metadata as SMEM scalars. Every elementwise op
runs on a dense [8, 512] tile regardless of how many shapes exist.

Bit-exactness: identical uint32 arithmetic to the jnp path — the oracle
tests assert match equality against ops.shapes.shape_match's fold, so
either backend can serve the same tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from emqx_tpu.ops.shapes import _fold, _homes

_U = np.uint32

SB = 8          # sublanes per batch block
BL_MAX = 512    # max lanes per batch block (block routes SB*BL topics)


def _seed_scalar(s: int, c1: int, c2: int) -> np.uint32:
    """_seed for a static shape id (same uint32 wraparound as ops.shapes,
    via masked python ints — numpy warns on scalar uint32 overflow)."""
    h = (s * c1 + c2) & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x7FEB352D) & 0xFFFFFFFF
    return _U(h ^ (h >> 13))


def _fold_kernel(L: int, NB: int, NSc: int, BL: int,
                 spm_ref, slen_ref, shh_ref, swr_ref,
                 topics_ref, lens_ref, dollar_ref,
                 h1_ref, h2_ref, b1_ref, b2_ref, compat_ref):
    lens_ = lens_ref[0]                       # [SB, BL]
    dollar = dollar_ref[0]
    for s in range(NSc):                      # static unroll over shapes
        slen = slen_ref[s]                    # SMEM scalars
        pmask = spm_ref[s]
        h1 = jnp.full((SB, BL), _seed_scalar(s, 0x27D4EB2F, 0x165667B1))
        h2 = jnp.full((SB, BL), _seed_scalar(s, 0x85EBCA6B, 0xC2B2AE3D))
        for l in range(L):
            concrete = (l < slen) & ((pmask >> l) & 1 == 0)   # scalar bool
            w = topics_ref[l, 0].astype(jnp.uint32)           # [SB, BL]
            h1 = jnp.where(concrete, _fold(h1, w, 2 * l), h1)
            h2 = jnp.where(concrete, _fold(h2, w, 2 * l + 1), h2)
        # int32 arithmetic throughout: Mosaic cannot truncate i8->i1, so
        # boolean select/and chains must stay integer-typed in-kernel
        len_ok = jnp.where(shh_ref[s] == 1,
                           (lens_ >= slen).astype(jnp.int32),
                           (lens_ == slen).astype(jnp.int32))
        real_shape = (slen >= 0).astype(jnp.int32)
        dollar_block = ((dollar != 0)
                        & (swr_ref[s] == 1)).astype(jnp.int32)
        nonempty = (lens_ > 0).astype(jnp.int32)
        compat = len_ok * real_shape * (1 - dollar_block) * nonempty
        b1, b2 = _homes(h1, h2, NB)
        h1_ref[s, 0] = h1.astype(jnp.int32)
        h2_ref[s, 0] = h2.astype(jnp.int32)
        b1_ref[s, 0] = b1.astype(jnp.int32)
        b2_ref[s, 0] = b2.astype(jnp.int32)
        compat_ref[s, 0] = compat


@functools.partial(jax.jit,
                   static_argnames=("L", "NB", "interpret"))
def shape_fold_pallas(topics: jax.Array, lens: jax.Array,
                      is_dollar: jax.Array, spm: jax.Array,
                      slen: jax.Array, shh: jax.Array, swr: jax.Array,
                      *, L: int, NB: int, interpret: bool = False):
    """Fused fold: -> (h1, h2, b1, b2, compat) each [B, NSc] int32.

    `interpret=True` runs the kernel in the Pallas interpreter (tests on
    a backend without Mosaic pass it); the kernel never infers it."""
    B = topics.shape[0]
    NSc = spm.shape[0]
    # lanes shrink for small batches (min native tile 8x128) so a 257-row
    # call pads to 1024, not SB*BL_MAX=4096
    BL = min(BL_MAX, max(128, 1 << max(0, (-(-B // SB) - 1).bit_length())))
    blk = SB * BL
    nb = max(1, -(-B // blk))
    Bp = nb * blk
    if Bp != B:
        topics = jnp.pad(topics, ((0, Bp - B), (0, 0)))
        lens = jnp.pad(lens, (0, Bp - B))
        is_dollar = jnp.pad(is_dollar, (0, Bp - B))
    # lane-major staging: levels become rows, the batch becomes the
    # [SB, BL] native tile (cheap XLA transposes/reshapes around the
    # kernel, full VPU occupancy inside it)
    topics4 = topics.T.reshape(L, nb, SB, BL)
    lens3 = lens.astype(jnp.int32).reshape(nb, SB, BL)
    dollar3 = is_dollar.astype(jnp.int32).reshape(nb, SB, BL)

    grid = (nb,)
    out_shape = [jax.ShapeDtypeStruct((NSc, nb, SB, BL), jnp.int32)] * 5
    obspec = pl.BlockSpec((NSc, 1, SB, BL), lambda i: (0, i, 0, 0),
                          memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    h1, h2, b1, b2, compat = pl.pallas_call(
        functools.partial(_fold_kernel, L, NB, NSc, BL),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            sspec, sspec, sspec, sspec,
            pl.BlockSpec((L, 1, SB, BL), lambda i: (0, i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SB, BL), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SB, BL), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[obspec] * 5,
        interpret=interpret,
    )(spm, slen, shh, swr, topics4, lens3, dollar3)

    def back(x):        # [NSc, nb, SB, BL] -> [B, NSc]
        return x.reshape(NSc, Bp).T[:B]

    return tuple(back(x) for x in (h1, h2, b1, b2, compat))
