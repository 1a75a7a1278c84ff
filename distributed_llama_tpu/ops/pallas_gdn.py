"""The gated delta rule's decode step as a Pallas TPU kernel.

One call advances one linear-attention layer's state for every batch row by
one position (`ops/gated_delta.gdn_chunked` at one position is the same
arithmetic in `jax.numpy`). The state of all layers and rows, `rec [L, rows, dk, H*dv]`
float32, stays in HBM; the layer rides in as a scalar-prefetched index, a grid
step copies one row's `[dk, hc*dv]` block of it into VMEM (`hc` heads, whole
lanes), updates it and writes it back to the same place
(`input_output_aliases`), so the state crosses HBM once in and once out a
step and nothing else of `rec` moves.

Everything in the body is float32 on whole `[dk, hc*dv]` tiles: key index on
the sublanes, (head, value index) on the lanes. What the recurrence needs of
`k` and `q` there is each head's vector spread along its own `dv` lanes,
`Kx[i, (h, j)] = k[h, i]`. That spreading is a product with a 0/1 matrix
`E [hc, hc*dv]` on the MXU; to keep it exact the wrapper splits `k` and `q`
into three bfloat16 terms (`x = hi + mid + lo` to float32's last bit) stacked
along the contraction, so every product is a bfloat16 times 1 and the float32
accumulator adds three terms. The vectors along the lanes (`v`, the decay, the
step, a keep flag) arrive spread already, `[4, H*dv]` a row.

A row whose position is 0 starts from a zero state whatever the slot held
(`keep` 0); a row that is parked is passed `alpha` 1 and `beta` 0 and its state
is written back as it was read.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_BYTES = 1 << 20  # one state block in VMEM (in and out, two buffers each)


def gdn_head_chunk(n_heads: int, dk: int, dv: int):
    """Heads a grid step takes: the most that divide `n_heads`, fill whole
    lanes and keep a `[dk, hc*dv]` float32 block within `BLOCK_BYTES`; None
    where no count does (the caller then keeps `gated_delta.gdn_chunked`)."""
    if dk % 8:
        return None
    for hc in range(n_heads, 0, -1):
        if n_heads % hc == 0 and (hc * dv) % LANE == 0 and dk * hc * dv * 4 <= BLOCK_BYTES:
            return hc
    return None


def _split3(x):
    """f32 -> three bf16 terms that add up to it. The rounding is
    `lax.reduce_precision`, not a cast there and back: the TPU's compiler
    folds a float32 -> bfloat16 -> float32 pair away (excess precision is
    allowed by default), which left `hi = x`, no `mid` or `lo`, and a
    bfloat16 `k` in the kernel (chip_smoke read 2e-3 of the state, PR 36)."""
    rnd = lambda v: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)  # noqa: E731
    hi = rnd(x)
    mid = rnd(x - hi)
    lo = rnd(x - hi - mid)
    return tuple(t.astype(jnp.bfloat16) for t in (hi, mid, lo))


def _spread_operand(x, hc: int):
    """[rows, H, dk] f32 -> [rows, H/hc, dk, 3*hc] bf16: a chunk's heads on
    the lanes, the three terms side by side."""
    rows, H, dk = x.shape
    xt = jnp.transpose(x.reshape(rows, H // hc, hc, dk), (0, 1, 3, 2))
    return jnp.concatenate(_split3(xt), axis=-1)


def _kernel(l_ref, kT_ref, qT_ref, e_ref, vab_ref, s_ref, o_ref, s_out_ref):
    del l_ref  # folded into the state's block index
    vab = vab_ref[0]  # [4, lanes]: v, alpha, beta, keep
    v, alpha, beta, keep = vab[0:1], vab[1:2], vab[2:3], vab[3:4]
    e = e_ref[...]
    kx = jnp.dot(kT_ref[0, 0], e, preferred_element_type=jnp.float32)  # [dk, lanes]
    qx = jnp.dot(qT_ref[0, 0], e, preferred_element_type=jnp.float32)
    s = jnp.where(keep > 0.0, s_ref[0, 0], 0.0) * alpha
    r = jnp.sum(s * kx, axis=0, keepdims=True)
    u = beta * (v - r)
    s = s + kx * u
    o_ref[0] = jnp.sum(s * qx, axis=0, keepdims=True)
    s_out_ref[0, 0] = s


@partial(jax.jit, static_argnames=("head_chunk", "interpret"))
def gdn_decode_step(
    rec,  # [L, rows, dk, H*dv] f32: every layer's state, updated in place
    layer,  # scalar int32: which layer's
    q,  # [rows, H, dk] f32, normalized and scaled
    k,  # [rows, H, dk] f32, normalized
    v,  # [rows, H, dv] f32
    alpha,  # [rows, H] f32 decay (1 for a parked row)
    beta,  # [rows, H] f32 step (0 for a parked row)
    keep,  # [rows] bool: false = the row starts from a zero state
    head_chunk: int | None = None,
    interpret: bool = False,
):
    """Returns (o [rows, H, dv] f32, rec)."""
    L, rows, dk, hv = rec.shape
    H = q.shape[1]
    dv = hv // H
    hc = head_chunk or gdn_head_chunk(H, dk, dv)
    n_chunks = H // hc
    lanes = hc * dv

    def spread(x):  # [rows, H] -> [rows, H*dv]
        return jnp.repeat(x.astype(jnp.float32), dv, axis=1)

    vab = jnp.stack(
        [
            v.reshape(rows, hv).astype(jnp.float32),
            spread(alpha),
            spread(beta),
            jnp.broadcast_to(keep.astype(jnp.float32)[:, None], (rows, hv)),
        ],
        axis=1,
    )
    e = jnp.repeat(jnp.eye(hc, dtype=jnp.bfloat16), dv, axis=1)  # [hc, lanes]
    e3 = jnp.concatenate([e, e, e], axis=0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, dk, 3 * hc), lambda r, c, l: (r, c, 0, 0)),
            pl.BlockSpec((1, 1, dk, 3 * hc), lambda r, c, l: (r, c, 0, 0)),
            pl.BlockSpec((3 * hc, lanes), lambda r, c, l: (0, 0)),
            pl.BlockSpec((1, 4, lanes), lambda r, c, l: (r, 0, c)),
            pl.BlockSpec((1, 1, dk, lanes), lambda r, c, l: (l[0], r, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lanes), lambda r, c, l: (r, 0, c)),
            pl.BlockSpec((1, 1, dk, lanes), lambda r, c, l: (l[0], r, 0, c)),
        ],
    )
    o, rec = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1, hv), jnp.float32),
            jax.ShapeDtypeStruct(rec.shape, rec.dtype),
        ],
        # operands count the scalar-prefetched layer: rec is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="gdn_decode_step",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        _spread_operand(k, hc), _spread_operand(q, hc), e3, vab, rec,
    )
    return o.reshape(rows, H, dv), rec
