"""Mamba-2's selective state space: a linear layer's recurrence, in two forms.

Dao, Gu, "Transformers are SSMs" (arXiv:2405.21060). A head `h` of `P`
channels keeps a state `S_h` in R^{N x P} (zero at the sequence's start); one
`B` and one `C` in R^N a position serve every head. At every position, with a
step `dt_h > 0`, a rate `A_h < 0` and a skip `D_h`:

    S_h <- exp(dt_h A_h) S_h + B (dt_h x_h)^T;    y_h = S_h^T C + D_h x_h

so a head forgets by one scalar a position and every channel of it takes the
same `B`-weighted input. Float32 throughout, products at `highest` precision;
the tests hold both forms to the recurrence written out position by position
(`testing.ssd_recurrence`):

* `ssd_chunked`: the chunked ("dual") form for a prompt's chunk, and for one
  position where the kernel does not apply (off the TPU, or one row against
  the batch's slots). Inside a sub-chunk of `sub` positions
  `Y = (C B^T . L) (dt x)` with `L_ts = exp(sum_{s<r<=t} dt_r A)` for
  `s <= t`, plus what the carried state gives, `exp(sum_{r<=t} dt_r A) S_0^T
  C_t`; the state crosses a sub-chunk's edge by the sub-chunk's total decay.
  The `[heads, sub, sub]` decay matrix is 1 MB a row at 64 heads and 64
  positions.
* `ssd_decode_step`, a Pallas TPU kernel: one position for every row, what a
  batch-decode step takes. The state of all layers and rows, `rec [L, rows,
  N, H*P]` float32, stays in HBM; the layer rides in as a scalar-prefetched
  index, a grid step copies one row's `[N, hc*P]` block of it into VMEM (`hc`
  heads, whole lanes), updates it and writes it back to the same place
  (`input_output_aliases`), so the state crosses HBM once in and once out a
  step and nothing else of `rec` moves. Every operand is float32 and meets
  the state on the vector unit: what runs along the lanes (`alpha = exp(dt
  A)`, `u = dt x`, `D x`, a keep flag) arrives spread already, `[4, H*P]` a
  row; `B` and `C` arrive as columns, `[N, 2]` a row, and a column times a row
  is the outer product. No operand passes the MXU, so none is rounded.

The state's layout is the delta rule's (`ops/gated_delta.py`): state index on
the sublanes, (head, channel) on the lanes.

A position that is not valid (a chunk's padding, a parked row) is passed with
`dt` 0: decay 1, no input, the state as it was; its output is not read.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import HP, SUB_CHUNK
from .pallas_gdn import gdn_head_chunk as ssd_head_chunk  # noqa: F401 — the same rule


def ssd_chunked(S, x, B, C, dt, A, D, sub: int = SUB_CHUNK):
    """The chunked form over a chunk (module docstring). S [b, N, H*P];
    x [b, t, H, P]; B, C [b, t, N]; dt [b, t, H] (0 where not valid); A, D
    [H]. Returns (y [b, t, H, P], S). `t` is a multiple of `min(sub, t)`.

    One B and one C serve every head, so what meets the state is a plain
    matrix product over its lanes as they lie (`C S` for the read-out, `B^T
    (w u)` for the update, the heads' decays spread along the lanes): the
    state is never cut into heads, and the program keeps the layout the
    decode kernel reads (cut into `[H, N, P]`, the TPU's compiler re-laid
    all rows' states out at every call: 2.3 GB in and out, seen compiling
    Granite's prompt chunk for a v5e)."""
    b, t, H, P = x.shape
    Cs = min(sub, t)
    if t % Cs:
        raise ValueError(f"a chunk of {t} positions is not whole sub-chunks of {Cs}")
    n = t // Cs

    def split(v):  # [b, t, ...] -> [n, b, Cs, ...]
        return jnp.moveaxis(v.reshape(b, n, Cs, *v.shape[2:]), 1, 0)

    def lanes(v):  # [..., H] -> [..., H*P]: a head's value on each of its lanes
        return jnp.repeat(v, P, axis=-1)

    tri = jnp.tril(jnp.ones((Cs, Cs), bool))[None, :, :, None]

    def body(S, xs):
        x_c, B_c, C_c, dt_c = xs
        gc = jnp.cumsum(dt_c * A, axis=1)  # [b, Cs, H]: log decay since the edge
        diff = gc[:, :, None, :] - gc[:, None, :, :]  # [b, t, s, H]
        L = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        cb = jnp.einsum("btn,bsn->bts", C_c, B_c, precision=HP)
        u = dt_c[..., None] * x_c  # [b, s, H, P]
        y = jnp.einsum("btsh,bshp->bthp", cb[..., None] * L, u, precision=HP)
        carried = lanes(jnp.exp(gc)) * jnp.einsum("btn,bnl->btl", C_c, S, precision=HP)
        g_last = gc[:, -1:, :]
        wu = (jnp.exp(g_last - gc)[..., None] * u).reshape(b, Cs, H * P)
        S = lanes(jnp.exp(g_last)) * S + jnp.einsum("bsn,bsl->bnl", B_c, wu, precision=HP)
        return S, y + carried.reshape(b, Cs, H, P)

    S, y = jax.lax.scan(body, S, (split(x), split(B), split(C), split(dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, H, P)
    return y + D[:, None] * x, S


def _kernel(l_ref, bc_ref, lanes_ref, s_ref, o_ref, s_out_ref):
    del l_ref  # folded into the state's block index
    v = lanes_ref[0]  # [4, lanes]: alpha, u, D x, keep
    alpha, u, dx, keep = v[0:1], v[1:2], v[2:3], v[3:4]
    bc = bc_ref[0]  # [N, 2]
    s = jnp.where(keep > 0.0, s_ref[0, 0], 0.0) * alpha + bc[:, 0:1] * u
    o_ref[0] = jnp.sum(s * bc[:, 1:2], axis=0, keepdims=True) + dx
    s_out_ref[0, 0] = s


@partial(jax.jit, static_argnames=("head_chunk", "interpret"))
def ssd_decode_step(
    rec,  # [L, rows, N, H*P] f32: every layer's state, updated in place
    layer,  # scalar int32: which layer's
    x,  # [rows, H, P] f32
    B,  # [rows, N] f32
    C,  # [rows, N] f32
    dt,  # [rows, H] f32 step (0 for a parked row)
    A,  # [H] f32, negative
    D,  # [H] f32
    keep,  # [rows] bool: false = the row starts from a zero state
    head_chunk: int | None = None,
    interpret: bool = False,
):
    """Returns (y [rows, H, P] f32, rec)."""
    L, rows, N, hp = rec.shape
    H = x.shape[1]
    P = hp // H
    hc = head_chunk or ssd_head_chunk(H, N, P)
    lanes = hc * P
    x = x.astype(jnp.float32)

    def spread(v):  # [rows, H] -> [rows, H*P]
        return jnp.repeat(v.astype(jnp.float32), P, axis=1)

    along = jnp.stack(
        [
            spread(jnp.exp(dt * A)),
            (dt[..., None] * x).reshape(rows, hp),
            (D[:, None] * x).reshape(rows, hp),
            jnp.broadcast_to(keep.astype(jnp.float32)[:, None], (rows, hp)),
        ],
        axis=1,
    )
    bc = jnp.stack([B, C], axis=-1).astype(jnp.float32)  # [rows, N, 2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, H // hc),
        in_specs=[
            pl.BlockSpec((1, N, 2), lambda r, c, l: (r, 0, 0)),
            pl.BlockSpec((1, 4, lanes), lambda r, c, l: (r, 0, c)),
            pl.BlockSpec((1, 1, N, lanes), lambda r, c, l: (l[0], r, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lanes), lambda r, c, l: (r, 0, c)),
            pl.BlockSpec((1, 1, N, lanes), lambda r, c, l: (l[0], r, 0, c)),
        ],
    )
    y, rec = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1, hp), jnp.float32),
            jax.ShapeDtypeStruct(rec.shape, rec.dtype),
        ],
        # operands count the scalar-prefetched layer: rec is the fourth
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="ssd_decode_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), bc, along, rec)
    return y.reshape(rows, H, P), rec
