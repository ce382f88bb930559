"""Causal softmax attention whose scores never reach HBM.

``softmax(causal(q k^T + q_shared k_shared^T) * scale) v``: the score of
latent attention (DeepSeek-V2's MLA; Kimi Linear runs it without rotary,
GLM-4.7-Flash with) has two parts, a head's own ``q_nope . k_nope`` and
``q_pe . k_pe`` against a key part that all heads share.  Whole, the
scores of 32 heads over 8,192 tokens are 8.6 GB in float32.

**A key span** (`window`): query ``i`` sees key ``j`` iff ``0 <= i - j <
window``, the query's own key among them; without one, the whole causal
past.  Both forms take it, and grouped-query attention comes through the
same function with no shared key part (``models/token_blocks.py::
GQAMixer``: afmoe's window and full layers, Nemotron-H's full ones,
lfm2_moe's): `k` and `v` come with the key-value heads they have, fewer
than `q`'s, key-value head ``g`` serving the query heads ``[g * n, (g + 1)
* n)``.

**Two forms, chosen by shape** (:func:`_fused_tile`).  Where a head's
values are whole lanes (a multiple of 128) and its own key at least 128
wide — or its key and values are exactly 64 wide each, with no shared key
part and an even head count (*paired heads*, below) — the sequence at
least two tiles long and one head's (one pair's) whole sequence inside
the kernels' VMEM budget (2 x 4 x T x (2 x key width + value width) bytes,
and 4 x T x (key width + value width) more where a key-value head serves
a group, within 78.6 MB: 12,800 tokens at a key and values of 256 + 256,
25,600 at 128 + 128 and at a pair of 64 + 64, 19,200 at 128 + 128 under a
group) — the configurations' shapes: 128 + 64
/ 128 and 192 + 64 / 256 at 8,192 tokens, 128 / 128 at 8,192 and at
16,384, 64 / 64 at 16,384 — :func:`blocked_causal_attention` runs a pair
of fused TPU kernels under a ``jax.custom_vjp`` (:func:`_fused_attention`;
interpreted where the backend is no TPU, so a CPU test runs the code the
chip runs).  The tile
follows from the sequence length (512, else 256 or 128: the largest that
divides it at least twice).  One product a tile serves both parts of the
score: every head's key holds the shared key part behind its own, 256
lanes a head in both configurations.  *Who lays it there follows from the
shapes.*  A caller whose heads are whole lanes with the shared part among
them (``nope + pe`` a multiple of 128, GLM-4.7-Flash's 192 + 64;
``models/token_blocks.py::MLAMixer``) hands the heads side by side with
room for it — the query's part in `q`'s last lanes a head, zeros in `k`'s
— and the part itself beside them (``heads=``, ``k_shared=``, ``theta=``):
the core's row passes (``ops/mlarows.py``, inside the ``custom_vjp``: the
operands' rounding is their store, or the product's own, and no cotangent
is rounded) add it into every head's lanes, turn it and the queries' by
position, and their transpose sums the part's gradient over the heads — no
array is cut into heads and none padded in HBM.  Any other caller (Kimi Linear's 128 + 64,
which is no whole lanes) hands ``[B, T, H, D]`` with the parts apart
(``q_shared=``, ``k_shared=``, already turned): here the shared key part
is then copied to every head and the key padded with zeros to whole lanes
in HBM (42 MB a layer in bfloat16), and differentiating that copy sums the
part's gradient over the heads.  The kernels read and write ``[B, T, H *
D]``, the heads side by side as a projection of that width writes them: a
head's tile is a strided block of 128-lane rows, and nothing is
transposed.  ``[B, T, H, D]`` in is the same bytes (and so is the result
cut into heads again), but what XLA does with an array of that shape
round the kernels is its own affair — it tiles ``(H, D)``, or puts the
tokens along the lanes for a norm a head, and moves every operand and
every result between that and the kernels' rows (57.8 ms of a 752 ms step
in `trinity_mini_train`, PERF.md section 6, PR 50).  A caller that has the
rows hands them over as they are (``heads=``) and gets rows back
(``models/token_blocks.py::GQAMixer``, whose norm a head and rotation are
``ops/headnorm.py``'s pass over the same rows; ``MLAMixer`` at whole lanes
a head, above).

*A group's key-value head by index map.*  Where `k` and `v` have fewer
heads than `q`, the kernels' grid runs over the query heads and a block
of `k`, `v` is grid head ``h``'s ``h // n`` of ``[B, T, G * D]``: the ``n``
grid steps of a group that follow one another name the same block, so the
pipeline fetches it once, and no repeat is written to HBM (537 MB a layer
in float32 at 16,384 tokens and 32 heads of 128 on 4).  No repeat is
differentiated either, so the backward kernel owns the group's sum: its
``dk``, ``dv`` blocks are the key-value head's whole sequence ``[T, D]`` in
float32, indexed by the group — they stay in VMEM while the group's query
heads go by (the first head's share of a key tile is written, the others'
added), as ``dq`` stays while a head's key tiles go by, and leave for HBM
once a group, in one buffer each (there is nothing to fetch and one write
a group to wait for: 16.8 MB at 16,384 tokens beside the 50.3 of ``q``,
the cotangent and ``dq``).  The grid's head axis is then sequential.
``heads == kv_heads`` is the identity map and every block what it was: one
path, by the shapes.  Only the order of the group's sum in ``dk``, ``dv``
differs from what differentiating a repeat gave; the forward output is the
repeat's bit for bit.

*Paired heads.*  A head of 64 is half a row of lanes, and a block's last
dimension has to be whole rows: the kernels then take **two adjacent
heads to a 128-lane block** of ``[B, T, H * 64]`` as it lies — heads ``2p``
and ``2p + 1``, a grid step a pair — and nothing is padded or copied in
HBM.  Inside a step the pair's queries (forward; keys and values
backward) are stacked (:func:`_stack_pair`): the first head's rows with
the second head's lanes at zero over the second head's rows with the
first's at zero, so that one 128-wide product against the block as it
lies gives each head's scores from its own half of the lanes, each head
has its own running maximum, sum and log-sum-exp (rows ``[B, H, N,
tile]`` as for any head), and a product over the stacked rows adds each
head's share of ``dq`` into its own lanes; the outputs, ``dk`` and ``dv``
are folded back to the block (:func:`_fold_pair`).  Half of every
128-wide product is zeros: the MXU does a head of 128's work for a head
of 64's mathematics, which the cores' share of their roofline shows.
Each head has keys and values of its own in its half: a key-value head of
64 is half a block, so for pairs alone a group's key-value heads are still
repeated to every query head in HBM, in front of the kernels (laying one
half over both in VMEM would be a lane shuffle of every key tile in every
step; no cell's gain rests on it).  An odd head count, a width of 64
beside a shared key part or
beside values of another width, and any other width under 128 fall back
to the XLA form.

*Forward* (:func:`_forward_kernel`): a grid step is one tile of one
head's queries.  That head's keys and values, the whole sequence, sit in
VMEM (fetched once a head: 8 MB in bfloat16), and the step walks the key
tiles up to the diagonal — a loop whose length is the step's own, so the
tiles above the diagonal are never met and only the one on it is masked
— with a running maximum, a running sum and the output rescaled as the
maximum grows (the online softmax).  A tile's scores ``[512, 512]`` live
in VMEM from their product to their weighted sum.  Out come the
attention's output and every row's log-sum-exp ``[B, H, T]``.  Under a
key span the walk starts ``ceil((window - 1) / tile)`` tiles under the
diagonal and not at 0, so the tiles behind the band are never met
either: the diagonal tile first (every row has a key in it, so the
running maximum is finite from then on), the tiles wholly inside the
band unmasked, the one at the band's trailing edge (two where the span
is no whole number of tiles) masked by the span — ``key > query``,
strictly, where the span is whole tiles.

*Backward* (:func:`_backward_kernel`), written by hand: a grid step is
one tile of one head's keys against that head's query tiles from the
diagonal on — under a key span, up to the last query tile whose band
reaches it, ``ceil((window - 1) / tile)`` past its own — with ``q``, the
output's cotangent and ``dq`` whole in VMEM (``dq`` gathers every key
tile's share there and is written once a head).  A tile's probabilities
are computed again from ``q``, ``k`` and
the kept log-sum-exp, and the five products of a tile (scores, the
weights' cotangent, ``dv``, ``dk``, ``dq``) follow.  The tile is held
keys-by-queries: what belongs to a query (its log-sum-exp, ``delta =
sum(o * do)``) is then a row, and no sum runs along the lanes.  The
residuals are the operands as the products take them, the output and the
log-sum-exp: no probability is kept.

*What a checkpoint keeps.*  The kernels are not under a ``jax.checkpoint``
of their own, but a model's block is (``nn.remat``), and a block computed
again runs the forward rule again to rebuild these residuals.  So the rule
names its two products (``checkpoint_name``: :data:`OUT_NAME`, the float32
output as the kernel wrote it — ``delta`` is taken from it, a rounded copy
would be another gradient — and :data:`LSE_NAME`, the rows' log-sum-exp),
and returns the named output as the primal one too, so that what reads it
(a gate, ``o_proj``) reads the kept array.
``models/token_blocks.py::remat_block`` wraps a block with the policy that
keeps the two (``4 * T * H * Dv + 4 * H * T`` bytes a core: 268 MB at
16,384 tokens and 32 heads of 128): the backward kernel then takes them
from the primal pass, the kernel in the block computed again has no
consumer left and is removed as dead code — the forward kernel runs once a
step.  The operands are still computed again, as the rest of the block is
(projections, norms, rotary, the rounding to bfloat16): the backward
kernel takes ``q``, ``k``, ``v`` from there.  A
name is the identity in the lowered program: with no such policy round the
call (``remat: false``, a forward-only program) nothing changes.

Any other shape (the tests' heads of 8 / 5 / 4, a sequence under two
tiles or one no tile divides) takes :func:`_blocked_xla`: queries in
blocks, one whole softmax a block, inside a ``lax.scan`` — a loop, so
that one block's scores are live at a time in the forward pass and in
the backward pass alike.  A block's body is wrapped in
``jax.checkpoint``.  A loop has one shape for all its steps, so the
sequence is cut into `spans` static spans, each with a loop of its own
over keys that end where the span ends: with four spans 62.5% of the full
square is computed.  `k_shared` is taken apart there and never copied.
A key span is a mask there and skips nothing.

The products take the ambient matmul precision, as an ``einsum`` does
(``ops/kda.py``'s rule): on the chip by default the kernels round their
operands to bfloat16 (in HBM, once: the residuals are the rounded
copies) and sum in float32, the softmax is float32; under
``jax.default_matmul_precision("highest")`` every product is float32.

``faa_mla_attention_traces_total{form}`` counts, at trace time, which
form a program got: ``fused`` or ``blocked_xla``;
``faa_attention_cores_traced_total{form, span}`` the same by key span
(``none`` without one), and ``faa_attention_key_tiles_total{span, kind}``
the key tiles a core's loops meet over one head's sequence (``visited``)
beside the causal half's (``causal``): 150 of 528 at 16,384 tokens, a
tile of 512 and a span of 2,048.
``faa_attention_head_blocks_traced_total{heads_a_block}`` counts the fused
cores by the heads a block of their kernels holds (``1``, or ``2`` for
paired heads of 64), ``faa_attention_kv_heads_mapped_total{group}`` by the
query heads their index maps give a key-value head (``1`` where every head
has its own, and for pairs), and
``faa_attention_kv_repeat_bytes_saved_total`` adds up the bytes of `k` and
`v` a repeat would have written for them.
``faa_attention_operands_traced_total{mixer, form}`` counts the fused cores
by whom they serve (``mla``: a shared key part; ``gqa``: none) and how
their operands came: ``rows``, the projections' own, or ``cut`` into heads.
``faa_attention_outputs_named_total{span}`` counts the cores whose forward
rule named its products — the cores offered to a policy — and
``faa_attention_kept_bytes_total{span}`` the bytes of the two arrays, what
keeping them costs; that a policy took them shows in the program (the
forward kernel once a core).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.ops import kda, mlarows

__all__ = ["blocked_causal_attention", "DEFAULT_QUERY_BLOCK", "DEFAULT_SPANS",
           "OUT_NAME", "LSE_NAME"]

DEFAULT_QUERY_BLOCK = 512
DEFAULT_SPANS = 4
#: what the fused forward rule names (``checkpoint_name``) for a checkpoint
#: policy to keep: the kernel's output and the rows' log-sum-exp
OUT_NAME = "faa_attention_out"
LSE_NAME = "faa_attention_lse"
LANES = kda.LANES
#: a head width the kernels take two to a block of :data:`LANES`
HALF = LANES // 2
#: the tiles of the fused kernels, largest first: a sequence takes the
#: first that divides it at least twice
TILES = (512, 256, 128)
#: what the kernels may keep in VMEM (the v5e has 128 MiB)
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def blocked_causal_attention(q, k, v, *, scale: float, q_shared=None,
                             k_shared=None, block: int = DEFAULT_QUERY_BLOCK,
                             spans: int = DEFAULT_SPANS, window: int | None = None,
                             heads: int | None = None, theta: float | None = None):
    """``softmax(causal(q k^T + q_shared k_shared^T) * scale) v``.

    `q` ``[B, T, H, D]``, `k` ``[B, T, G, D]``, `v` ``[B, T, G, Dv]`` with H
    a multiple of G (key-value head ``g`` serves the query heads ``[g * n,
    (g + 1) * n)``); `q_shared` ``[B, T, H, Ds]`` with `k_shared` ``[B, T,
    Ds]`` (one key part for all heads, ``G == H``), or neither.  Returns
    ``[B, T, H, Dv]``.  With `heads` = H the three are the heads side by
    side as a projection writes them, ``[B, T, H * D]``, ``[B, T, G * D]``
    and ``[B, T, G * Dv]``, and so is the result, ``[B, T, H * Dv]``: the
    fused kernels' own rows, never cut into heads on the way.  Side by side
    a shared key part lies *inside* every head's lanes, its last ``Ds``: `q`
    comes with the query's part there (no `q_shared`), `k` with zeros there
    (zero weight columns) and `k_shared` beside it, which the core's row
    passes (``ops/mlarows.py``) add into those lanes of every head — both
    turned by position there where `theta` is given (interleaved pairs, in
    place), and all three written as the products take them.  `block` and
    `spans` shape the XLA form alone; the kernels' tile follows from the
    shapes.  `window`: the key span, query ``i`` sees key ``j`` iff ``0 <=
    i - j < window`` (its own among them); None, or a span the sequence
    does not outgrow: the whole causal past."""
    batch, length = q.shape[:2]
    rows = heads is not None
    # the shared part in every head's own lanes: laid there by the row passes
    lay = (k_shared.shape[-1], theta) if rows and k_shared is not None else None
    if theta is not None and lay is None:
        raise ValueError("only a shared key part inside the heads' lanes is turned here")
    if rows:
        if q_shared is not None:
            raise ValueError("heads side by side carry no shared key part")
        dim = q.shape[-1] // heads
        kv_heads = k.shape[-1] // dim

        def cut(a, n):               # off the kernels' path: XLA moves the array
            return a.reshape(batch, length, n, a.shape[-1] // n)
    else:
        heads, kv_heads, dim = q.shape[2], k.shape[2], q.shape[3]
        cut = lambda a, n: a
    group = heads // kv_heads
    vdim = math.prod(v.shape[2:]) // kv_heads
    if group * kv_heads != heads or math.prod(k.shape[2:]) != kv_heads * dim:
        raise ValueError(f"queries {q.shape} on keys {k.shape}, values {v.shape}: "
                         "no whole number of query heads a key-value head")

    def every_head(a):               # key-value head g for the query heads [g * n, (g + 1) * n)
        a = cut(a, kv_heads)
        return a if group == 1 else jnp.repeat(a, group, axis=2)

    if window is not None:
        if window < 1:
            raise ValueError(f"window={window}: a query sees its own key at least")
        if window >= length:
            window = None
    shared = 0 if q_shared is None else q_shared.shape[-1]
    if lay is None:
        tile = _fused_tile(length, heads, group, dim, vdim, shared)
    elif mlarows.admits(dim, lay[0]):
        tile = _fused_tile(length, heads, group, dim - lay[0], vdim, lay[0])
    else:
        tile = None
    paired = tile is not None and vdim == HALF
    # trace time: which form each program that holds an attention core got
    form = "blocked_xla" if tile is None else "fused"
    telemetry.registry().counter(
        "faa_mla_attention_traces_total", "latent-attention cores traced into a "
        "program, by the form that computes them", form=form).inc()
    telemetry.registry().counter(
        "faa_attention_cores_traced_total", "attention cores traced into a "
        "program, by the form that computes them and their key span",
        form=form, span=_span_label(window)).inc()
    if tile is None:
        if lay is not None:
            q, k = map(_flat, _laid_xla(cut(q, heads), cut(k, heads), k_shared, theta))
            k_shared = None
        out = _blocked_xla(cut(q, heads), every_head(k), every_head(v), q_shared, k_shared,
                           scale, block, spans, window)
        return _flat(out) if rows else out
    _count_key_tiles(window, *key_tiles(length, tile, window))
    telemetry.registry().counter(
        "faa_attention_head_blocks_traced_total", "fused attention cores traced "
        "into a program, by the heads a 128-lane block of their kernels holds",
        heads_a_block="2" if paired else "1").inc()
    telemetry.registry().counter(
        "faa_attention_operands_traced_total", "fused attention cores traced into a "
        "program, by the mixer they serve (mla: a shared key part) and how their "
        "operands came: the projections' own rows, or cut into heads",
        mixer="gqa" if q_shared is None and lay is None else "mla",
        form="rows" if rows else "cut").inc()
    if paired and group > 1:
        # a key-value head of 64 is half a block of lanes: repeated in HBM, the
        # pair path as it was before the kernels mapped a group to its head
        k, v, group, kv_heads = every_head(k), every_head(v), 1, heads
    _count_mapped(group, k, v)
    if shared:
        # the shared key part, copied to every head: one product a tile
        # then serves both parts of the score, and differentiating this
        # line sums the part's gradient over the heads
        q = jnp.concatenate([q, q_shared], -1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared[:, :, None], (batch, length, heads, shared))], -1)
    short = -(dim + shared) % LANES   # zeros: they add nothing to a score
    if short and not paired:          # a pair of heads of 64 is whole lanes as it lies
        q, k = (jnp.pad(cut(a, n), ((0, 0),) * 3 + ((0, short),))
                for a, n in ((q, heads), (k, kv_heads)))
    # float32 in, whatever the model's activations are: under `highest` the
    # kernels' products are float32 ones, which Mosaic refuses bfloat16
    # operands for (a model in ``precision: bf16`` under a float32 comparison)
    q, k, v = (_flat(a).astype(jnp.float32) for a in (q, k, v))
    out = _fused_attention(q, k, v, None if lay is None else k_shared.astype(jnp.float32),
                           float(scale), tile, heads, kda._float32_products(),
                           not kda._on_tpu(), window, lay)
    return out if rows else out.reshape(batch, length, heads, vdim)


def _reach(window: int, tile: int) -> int:
    """How many key tiles below the diagonal one a query tile's band
    reaches: ``ceil((window - 1) / tile)``."""
    return -(-(window - 1) // tile)


def key_tiles(length: int, tile: int, window: int | None) -> tuple[int, int]:
    """``(visited, causal)``: the key tiles the fused kernels' loops meet
    over one head's sequence, and the causal half's (150 of 528 at 16,384
    tokens, a tile of 512 and a span of 2,048)."""
    count = length // tile
    causal = count * (count + 1) // 2
    if window is None:
        return causal, causal
    reach = _reach(window, tile)
    return sum(min(i, reach) + 1 for i in range(count)), causal


def _span_label(window: int | None) -> str:
    return "none" if window is None else str(window)


def _count_key_tiles(window: int | None, visited: int, causal: int) -> None:
    """Trace time: the key tiles a core's loops meet over one head's
    sequence, beside the causal half's."""
    for kind, tiles in (("visited", visited), ("causal", causal)):
        telemetry.registry().counter(
            "faa_attention_key_tiles_total", "key tiles the traced attention "
            "cores' loops meet over one head's sequence (visited), beside the "
            "causal half's", span=_span_label(window), kind=kind).inc(tiles)


def _count_named(window: int | None, kept_bytes: int) -> None:
    """Trace time: a core whose forward rule named its output and
    log-sum-exp, and the bytes of the two — what a policy that keeps them
    keeps."""
    telemetry.registry().counter(
        "faa_attention_outputs_named_total", "fused attention cores whose forward "
        "rule named the output and the log-sum-exp for a checkpoint policy to keep",
        span=_span_label(window)).inc()
    telemetry.registry().counter(
        "faa_attention_kept_bytes_total", "bytes of the output and the log-sum-exp "
        "those cores offer a checkpoint policy",
        span=_span_label(window)).inc(kept_bytes)


def _count_mapped(group: int, k, v) -> None:
    """Trace time: a fused core by the query heads its kernels' index maps
    give a key-value head, and the bytes of `k` and `v` as handed that a
    repeat to every query head would have written."""
    telemetry.registry().counter(
        "faa_attention_kv_heads_mapped_total", "fused attention cores traced into a "
        "program, by the query heads their kernels' index maps give a key-value head",
        group=str(group)).inc()
    telemetry.registry().counter(
        "faa_attention_kv_repeat_bytes_saved_total", "bytes of keys and values a "
        "repeat to every query head would have written for those cores").inc(
            group * (k.nbytes + v.nbytes) if group > 1 else 0)


def _fused_tile(length: int, heads: int, group: int, dim: int, vdim: int,
                shared: int = 0) -> int | None:
    """The tile of the fused kernels for `heads` query heads of `dim` (and
    a shared key part of `shared`) on values of `vdim`, `group` query heads
    a key-value head, over `length` tokens — or None where they take the
    XLA form.  Admitted: a head's values whole lanes (a multiple of 128)
    and its own key at least one row of them; or a key and values of
    exactly half a row (64) each, no shared key part and an even head
    count, which the kernels take two adjacent heads to a 128-lane block
    (:data:`HALF`).  Either way the sequence is at least two tiles, and
    what the kernels keep of one head's (one pair's) whole sequence fits
    VMEM.  Still falling back: any other width under 128, a width of 64
    beside a shared key part, beside values of another width or on an odd
    head count, and a shared key part beside grouped heads."""
    if vdim == HALF:
        if dim != HALF or shared or heads % 2:
            return None
        width = values = LANES        # a block is two heads side by side
        group = 1                     # whose key-value heads come repeated
    elif vdim % LANES or dim < LANES or (shared and group > 1):
        return None
    else:
        width = dim + shared
        width += -width % LANES
        values = vdim
    # the backward kernel's: q and the cotangent, float32 at the most, and
    # dq, each twice (the pipeline's two buffers); of a group the sums dk and
    # dv besides, once (:func:`_Blocks.gathered`)
    kept = 2 * 4 * length * (2 * width + values)
    if group > 1:
        kept += 4 * length * (width + values)
    if kept > VMEM_LIMIT_BYTES * 3 // 4:
        return None
    return next((t for t in TILES if length % t == 0 and length >= 2 * t), None)


# ------------------------------------------------------- the fused kernels
#
# Both kernels see the heads side by side, ``[B, T, H * D]``: a head's
# ``[tile, D]`` is then a block whose last dimension is whole lanes, fetched
# by a strided DMA, and nothing is transposed in HBM.

def _causal(tile: int, *, keys_first: bool, window: int | None = None):
    """``[tile, tile]``: whether the query sees the key, on the diagonal;
    under a `window` shorter than a tile the band cuts that tile too."""
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    seen = col >= row if keys_first else row >= col
    if window is not None and window < tile:
        seen &= _in_band(tile, 0, window, keys_first=keys_first)
    return seen


def _in_band(tile: int, apart, window: int, *, keys_first: bool):
    """``[tile, tile]``: whether the key is still inside the query's span
    of `window`, the query's tile `apart` tiles past the key's: ``apart *
    tile + query - key < window``.  At the band's trailing edge of a span
    that is whole tiles (``apart * tile == window``) that is ``key >
    query``, strictly: the causal mask's complement."""
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    ahead = col - row if keys_first else row - col        # query - key
    return ahead < window - apart * tile


def _as_row(column):
    """``[n, 1]`` -> ``[1, n]`` with nothing but masks and sums over
    sublanes: 128 rows at a time against the identity."""
    size = column.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    return jnp.concatenate([
        jnp.sum(jnp.where(row == col, column[first:first + LANES], 0.0), 0,
                keepdims=True)
        for first in range(0, size, LANES)], 1)


def _low_half(shape):
    """Whether a lane belongs to the first head of a pair's block."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < HALF


def _stack_pair(block):
    """A pair's ``[n, 128]`` block as ``[2n, 128]``: the first head's rows
    with the second head's lanes at zero, then the second head's with the
    first's at zero.  A product over the lanes against the pair's block
    as it lies is then each head's own, and a product over these rows adds
    each head's part into its own lanes."""
    low, zero = _low_half(block.shape), jnp.zeros_like(block)
    return jnp.concatenate([jnp.where(low, block, zero),
                            jnp.where(low, zero, block)], 0)


def _fold_pair(stacked):
    """``[2n, 128]``, a pair's heads one under the other, each with its
    result over all the lanes -> ``[n, 128]``: each head's own lanes."""
    half = stacked.shape[0] // 2
    return jnp.where(_low_half((half, LANES)), stacked[:half], stacked[half:])


def _twice(seen):
    """A tile's mask for a pair's two heads, one under the other."""
    return jnp.concatenate([seen, seen], 0)


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, scale: float, exact: bool,
                    window: int | None, pair: bool):
    """One tile of one head's queries against that head's keys up to the
    diagonal: `k_ref`, `v_ref` hold the head's whole sequence (fetched once
    a head), a tile of scores lives from its product to its weighted sum,
    and the softmax is the running one (maximum, sum, rescaled output).
    `lse_ref` ``[1, 1, N, tile]``, where a backward pass follows: every
    row's log-sum-exp, a tile a row.  With a `window` the walk starts
    ``ceil((window - 1) / tile)`` tiles under the diagonal and not at 0:
    the diagonal tile first (every row has a key in it, so the running
    maximum is finite from then on), the tiles wholly inside the band
    unmasked, the one or two at its trailing edge masked by the span.
    With `pair` the blocks hold two heads of 64 side by side: the queries
    are stacked (:func:`_stack_pair`), so a tile's scores, maxima, sums and
    outputs are ``[2 * tile, ...]``, the first head's rows over the
    second's, each against its own half of the keys' and values' lanes."""
    i = pl.program_id(2)
    tile = q_ref.shape[1]
    q = _stack_pair(q_ref[0]) if pair else q_ref[0]
    rows = q.shape[0]
    masked = _twice if pair else (lambda seen: seen)

    def against(j, carry, seen=None):
        top, total, out = carry
        keys = pl.ds(pl.multiple_of(j * tile, tile), tile)
        scores = kda._dot(q, k_ref[0, keys, :], kda._NT, exact) * scale
        if seen is not None:
            scores = jnp.where(masked(seen), scores, -jnp.inf)
        new_top = jnp.maximum(top, jnp.max(scores, -1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        weights = jnp.exp(scores - new_top)
        total = shrink * total + jnp.sum(weights, -1, keepdims=True)
        out = shrink * out + kda._dot(weights, v_ref[0, keys, :], kda._NN, exact)
        return new_top, total, out

    carry = (jnp.full((rows, 1), -jnp.inf, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, v_ref.shape[-1]), jnp.float32))
    if window is None:
        # the key tiles wholly below the diagonal, then the one on it
        carry = jax.lax.fori_loop(0, i, against, carry)
        top, total, out = against(i, carry, _causal(tile, keys_first=False))
    else:
        carry = against(i, carry, _causal(tile, keys_first=False, window=window))
        first, whole = _band(i, window, tile)
        carry = jax.lax.fori_loop(whole, i, against, carry)
        top, total, out = jax.lax.fori_loop(
            first, whole, lambda j, carry: against(j, carry, _in_band(
                tile, i - j, window, keys_first=False)), carry)
    if not pair:
        o_ref[0] = out / total
        for ref in lse_ref:
            ref[0, 0, pl.ds(i, 1), :] = _as_row(top + jnp.log(total))
        return
    o_ref[0] = _fold_pair(out / total)
    for ref in lse_ref:
        both = _as_row(top + jnp.log(total))                 # [1, 2 * tile]
        ref[0, 0, pl.ds(i, 1), :] = both[:, :tile]
        ref[0, 1, pl.ds(i, 1), :] = both[:, tile:]


def _band(i, window: int, tile: int):
    """``(first, whole)`` for the query tile `i` under a span of `window`:
    the first key tile its band reaches, and the first that lies wholly
    inside the band (the diagonal tile `i` where none below it does)."""
    first = jnp.maximum(i - _reach(window, tile), 0)
    return first, jnp.maximum(i - max(window // tile - 1, 0), first)


def _backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, *, scale: float, exact: bool,
                     window: int | None, pair: bool, group: int):
    """One tile of one head's keys against that head's queries from the
    diagonal on: `q_ref`, `do_ref` and `dq_ref` hold the head's whole
    sequence (`dq_ref` stays in VMEM while the key tiles go by and gathers
    every tile's share).  A tile's probabilities are computed again from
    `q`, `k` and the log-sum-exp, keys along the rows: what belongs to a
    query (`lse`, `delta = sum(o * do)`) is then a row vector, and no sum
    runs along the lanes.  With `pair` the keys and values of the block's
    two heads of 64 are stacked (:func:`_stack_pair`): a tile is ``[2 *
    tile keys, tile queries]``, each head's keys against the queries' and
    the cotangent's whole block, its own rows of `lse` and `delta`; `dq`
    gathers both heads' shares, each into its own lanes, and `dk`, `dv`
    are folded back to the block once the query tiles have gone by.  With
    a `group` of query heads on the key-value head `k_ref`, `v_ref` show,
    `dk_ref`, `dv_ref` hold that head's whole sequence and stay in VMEM
    while the group's query heads go by, as `dq_ref` does while a head's
    key tiles do: the first head's share of a tile is written, the others'
    added, and the group's sum leaves for HBM once."""
    j = pl.program_id(2)
    tile = k_ref.shape[1]
    count = q_ref.shape[1] // tile
    k, v = k_ref[0], v_ref[0]
    if pair:
        k, v = _stack_pair(k), _stack_pair(v)
        first_head = jax.lax.broadcasted_iota(jnp.int32, (2 * tile, tile), 0) < tile
    masked = _twice if pair else (lambda seen: seen)

    def of_queries(ref, i):
        """What belongs to the query tile `i`, a row a head."""
        if not pair:
            return ref[0, 0, pl.ds(i, 1), :]
        return jnp.where(first_head, ref[0, 0, pl.ds(i, 1), :], ref[0, 1, pl.ds(i, 1), :])

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def against(i, carry, seen=None):
        dk, dv = carry
        queries = pl.ds(pl.multiple_of(i * tile, tile), tile)
        q, do = q_ref[0, queries, :], do_ref[0, queries, :]
        scores = kda._dot(k, q, kda._NT, exact) * scale             # [keys, queries]
        weights = jnp.exp(scores - of_queries(lse_ref, i))
        if seen is not None:
            weights = jnp.where(masked(seen), weights, 0.0)
        d_weights = kda._dot(v, do, kda._NT, exact)
        d_scores = weights * (d_weights - of_queries(delta_ref, i)) * scale
        weights, d_scores = kda._operand(weights, exact), kda._operand(d_scores, exact)
        dq_ref[0, queries, :] += kda._dot(d_scores, k, kda._TN, exact)
        return (dk + kda._dot(d_scores, q, kda._NN, exact),
                dv + kda._dot(weights, do, kda._NN, exact))

    carry = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    if window is None:
        carry = against(j, carry, _causal(tile, keys_first=True))
        dk, dv = jax.lax.fori_loop(j + 1, count, against, carry)
    else:
        # the query tiles whose band reaches this key tile: its own, those
        # it lies wholly inside the band of, the one or two it is the
        # trailing edge of
        carry = against(j, carry, _causal(tile, keys_first=True, window=window))
        whole = jnp.minimum(j + max(window // tile, 1), count)
        carry = jax.lax.fori_loop(j + 1, whole, against, carry)
        dk, dv = jax.lax.fori_loop(
            whole, jnp.minimum(j + _reach(window, tile) + 1, count),
            lambda i, carry: against(i, carry, _in_band(
                tile, i - j, window, keys_first=True)), carry)
    if pair:
        dk, dv = _fold_pair(dk), _fold_pair(dv)
    if group == 1:
        dk_ref[0], dv_ref[0] = dk, dv
        return
    keys = pl.ds(pl.multiple_of(j * tile, tile), tile)
    first = pl.program_id(1) % group == 0

    @pl.when(first)
    def _():
        dk_ref[0, keys, :], dv_ref[0, keys, :] = dk, dv

    @pl.when(jnp.logical_not(first))
    def _():
        dk_ref[0, keys, :] += dk
        dv_ref[0, keys, :] += dv


class _Blocks:
    """What both kernels' ``pallas_call``s share: the grid (batch, head,
    tile of the sequence) and the blocks of ``[B, T, H * D]`` and of the
    rows' ``[B, H, N, tile]``.  `pair`: heads of 64, two to a block and to
    a grid step; `width` and `vdim`, a block's lanes of keys and of values,
    are then a pair's.  `group`: the query heads a key-value head, from the
    widths of `q` and `k`: a block of `k`, `v` (`of_group`) is the grid's
    head over `group`, so the `group` steps that follow one another name one
    block and it is fetched once; the identity where every head has its
    own."""

    def __init__(self, q, k, v, heads: int, tile: int, interpret: bool):
        self.batch, self.length, _ = q.shape
        self.heads, self.tile = heads, tile
        self.count = self.length // tile
        self.group = q.shape[-1] // k.shape[-1]
        self.pair = q.shape[-1] // heads == HALF
        self.heads_a_block = 2 if self.pair else 1
        self.width, self.vdim = (LANES, LANES) if self.pair else (
            q.shape[-1] // heads, v.shape[-1] * self.group // heads)
        self.options = dict(
            grid=(self.batch, heads // self.heads_a_block, self.count),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                # a group's sums of dk, dv pass from head to head in VMEM
                dimension_semantics=("parallel", "parallel" if self.group == 1 else
                                     "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES))

    def _head(self, of_group: bool):
        if of_group and self.group > 1:
            return lambda h: h // self.group
        return lambda h: h

    def one(self, width, of_group=False):       # a tile of one head
        head = self._head(of_group)
        return pl.BlockSpec((1, self.tile, width), lambda b, h, n: (b, n, head(h)))

    def whole(self, width, of_group=False, **how):     # one head's whole sequence
        head = self._head(of_group)
        return pl.BlockSpec((1, self.length, width), lambda b, h, n: (b, 0, head(h)), **how)

    def gathered(self, width):
        """Where the backward kernel puts `dk`, `dv`: a head's tile where
        every head has its own, else the key-value head's whole sequence,
        which stays while its group goes by — in one buffer, there being
        nothing to fetch and one write a group to wait for."""
        if self.group == 1:
            return self.one(width)
        return self.whole(width, of_group=True, pipeline_mode=pl.Buffered(1))

    @property
    def rows(self):             # of [B, H, N, tile]
        return pl.BlockSpec((1, self.heads_a_block, self.count, self.tile),
                            lambda b, h, n: (b, h, 0, 0))

    @property
    def rows_shape(self):
        return jax.ShapeDtypeStruct((self.batch, self.heads, self.count, self.tile),
                                    jnp.float32)


def _flat(a):
    """``[B, T, H, D]`` -> ``[B, T, H * D]``: the same bytes."""
    return a.reshape(a.shape[0], a.shape[1], -1)


# Jitted by themselves, as ``ops/kda.py``'s are: a model's blocks call them
# at the same shapes, and a ``jit`` inside a trace is traced and lowered to
# Mosaic once a program.


@functools.partial(jax.jit, static_argnames=("scale", "tile", "heads", "exact", "interpret",
                                             "keep", "window"))
def _forward(q, k, v, scale: float, tile: int, heads: int, exact: bool, interpret: bool,
             keep: bool, window: int | None):
    """``out [B, T, H * Dv]`` and, with `keep`, the rows' log-sum-exp ``[B,
    H, N, tile]``; `q`, `k`, `v` as the products take them."""
    blocks = _Blocks(q, k, v, heads, tile, interpret)
    width, vdim = blocks.width, blocks.vdim
    out_shape = [jax.ShapeDtypeStruct(
        (blocks.batch, blocks.length, v.shape[-1] * blocks.group), jnp.float32)]
    out_specs = [blocks.one(vdim)]
    if keep:
        out_shape.append(blocks.rows_shape)
        out_specs.append(blocks.rows)
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale, exact=exact, window=window,
                          pair=blocks.pair),
        out_shape=out_shape,
        in_specs=[blocks.one(width), blocks.whole(width, of_group=True),
                  blocks.whole(vdim, of_group=True)],
        out_specs=out_specs, name="mla_attention_forward", **blocks.options,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "heads", "exact", "interpret",
                                             "window"))
def _backward(q, k, v, out, lse, d_out, scale: float, tile: int, heads: int, exact: bool,
              interpret: bool, window: int | None):
    blocks = _Blocks(q, k, v, heads, tile, interpret)
    width, vdim = blocks.width, blocks.vdim
    # what every score of a row owes through the row's sum: over a head's
    # lanes, read where the rows lie (a head of 64 is no whole tile's lanes)
    owed = out * d_out
    owed = owed.reshape(blocks.batch, blocks.length, heads, HALF) if blocks.pair \
        else kda.by_tile(owed, heads)
    delta = jnp.sum(owed, -1).reshape(blocks.batch, blocks.length, heads).transpose(
        0, 2, 1).reshape(lse.shape)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale=scale, exact=exact, window=window,
                          pair=blocks.pair, group=blocks.group),
        out_shape=[like(q), like(k), like(v)],
        in_specs=[blocks.whole(width), blocks.one(width, of_group=True),
                  blocks.one(vdim, of_group=True), blocks.whole(vdim), blocks.rows,
                  blocks.rows],
        out_specs=[blocks.whole(width), blocks.gathered(width), blocks.gathered(vdim)],
        name="mla_attention_backward", **blocks.options,
    )(q, k, v, kda._operand(d_out, exact), lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _fused_attention(q, k, v, k_shared, scale: float, tile: int, heads: int, exact: bool,
                     interpret: bool, window: int | None, lay: tuple | None):
    """The causal softmax through the kernels, on the heads side by side:
    `q` ``[B, T, H * D]`` with D whole lanes (every part of the score in
    it) or half a row of them, `k` ``[B, T, G * D]``, `v` ``[B, T, G *
    Dv]``, float32; the output ``[B, T, H * Dv]``.  `exact`: float32
    products; `interpret`: no TPU to compile them for; `window`: the key
    span, shorter than the sequence, or None.  `lay` ``(Ds, theta)`` with
    `k_shared` ``[B, T, Ds]``, or neither: the shared key part still to be
    laid into every head's last ``Ds`` lanes and both sides turned there
    (:func:`_operands`)."""
    q, k, v = _operands(q, k, v, k_shared, heads, exact, interpret, lay)
    return _forward(q, k, v, scale, tile, heads, exact, interpret, keep=False,
                    window=window)[0]


def _angle(lay: tuple, length: int):
    """``[T, Ds / 2]``: what pair ``i`` of token ``t``'s shared part is turned
    by (``models/token_blocks.py::rotate_by_position``'s), or None."""
    shared, theta = lay
    if theta is None:
        return None
    inverse = theta ** (-jnp.arange(0, shared, 2, dtype=jnp.float32) / shared)
    return jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]


def _operands(q, k, v, k_shared, heads: int, exact: bool, interpret: bool, lay: tuple | None):
    """`q`, `k`, `v` as the products take them: rounded to bfloat16 unless
    `exact` — by whoever writes them last: the row passes that turn the
    queries and lay the shared key part (``ops/mlarows.py``) where there is
    one to lay."""
    if lay is None:
        return tuple(kda._operand(a, exact) for a in (q, k, v))
    angle = _angle(lay, q.shape[1])
    return (mlarows.turn(q, heads, angle, exact=exact, interpret=interpret),
            mlarows.lay(k, heads, k_shared, angle, exact=exact, interpret=interpret),
            kda._operand(v, exact))


def _fused_attention_fwd(q, k, v, k_shared, scale: float, tile: int, heads: int, exact: bool,
                         interpret: bool, window: int | None, lay: tuple | None):
    # kept as the products take them: bfloat16 unless `exact`
    q, k, v = _operands(q, k, v, k_shared, heads, exact, interpret, lay)
    out, lse = _forward(q, k, v, scale, tile, heads, exact, interpret, keep=True,
                        window=window)
    # named for a checkpoint policy to keep (``token_blocks.remat_block``):
    # the backward rule then takes these two from the primal pass, and the
    # kernel has no consumer left in what the policy computes again.  The
    # primal output is the named array too: what reads it reads the kept one
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    _count_named(window, out.nbytes + lse.nbytes)
    return out, (q, k, v, out, lse)


def _fused_attention_bwd(scale: float, tile: int, heads: int, exact: bool, interpret: bool,
                         window: int | None, lay: tuple | None, residuals, d_out):
    dq, dk, dv = _backward(*residuals, d_out, scale, tile, heads, exact, interpret,
                           window=window)
    if lay is None:
        return dq, dk, dv, None
    # the row passes' transpose: `dq` turned back where it was turned, the shared
    # part's gradient `dk`'s lanes there summed over the heads
    dq, d_shared = mlarows.unlay(dq, dk, heads, lay[0], _angle(lay, dq.shape[1]),
                                 interpret=interpret)
    return dq, dk, dv, d_shared


_fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


# ------------------------------------------------- the form in jnp and XLA

def _laid_xla(q, k, k_shared, theta: float | None):
    """The row passes' arithmetic in jnp, for the shapes the kernels do not
    take: `q` ``[B, T, H, D]`` with its last ``Ds`` channels turned, `k` with
    `k_shared` ``[B, T, Ds]`` (turned) in those channels of every head."""
    shared = k_shared.shape[-1]
    own = q.shape[-1] - shared
    q_shared, k_shared = q[..., own:], k_shared[:, :, None]
    angle = _angle((shared, theta), q.shape[1])
    if angle is not None:
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]

        def turned(x):              # pair (2i, 2i + 1), in place
            first, second = x[..., 0::2], x[..., 1::2]
            return jnp.stack([first * cos - second * sin,
                              first * sin + second * cos], -1).reshape(x.shape)

        q_shared, k_shared = turned(q_shared), turned(k_shared)
    return (jnp.concatenate([q[..., :own], q_shared], -1),
            jnp.concatenate([k[..., :own], jnp.broadcast_to(
                k_shared, k.shape[:-1] + (shared,))], -1))



def _attend(q, q_shared, k, k_shared, v, first, scale: float, window: int | None):
    """One block of queries, whose first token is token `first`, against
    the keys ``[0, k.shape[1])``; under a `window` the keys further back
    than the span are masked like those ahead."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if q_shared is not None:
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_shared, k_shared)
    rows = first + jnp.arange(q.shape[1])[:, None]
    seen = rows >= jnp.arange(k.shape[1])[None, :]
    if window is not None:
        seen &= rows - jnp.arange(k.shape[1])[None, :] < window
    scores = jnp.where(seen, scores.astype(jnp.float32) * scale, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _blocked_xla(q, k, v, q_shared, k_shared, scale: float, block: int, spans: int,
                 window: int | None):
    """:func:`blocked_causal_attention` for the shapes the kernels do not
    take: queries in blocks of `block`, inside `spans` ``lax.scan``s.  A
    `window` is a mask here and skips nothing: a block of queries meets
    every key up to the end of its scan's stretch, and the key-tile counter
    says so."""
    batch, length = q.shape[:2]
    block = min(block, length)
    if length % block:
        raise ValueError(f"sequence length {length} is no multiple of the "
                         f"query block {block}")
    blocks = length // block
    while blocks % spans:  # a short sequence has fewer blocks than spans
        spans -= 1
    per_span = blocks // spans
    _count_key_tiles(window, per_span * per_span * spans * (spans + 1) // 2,
                     blocks * (blocks + 1) // 2)
    attend = jax.checkpoint(_attend, static_argnums=(6, 7))

    def in_blocks(a, start, stop):  # [B, T, ...] -> [n, B, block, ...]
        a = a[:, start:stop]
        return jnp.moveaxis(a.reshape((batch, per_span, block) + a.shape[2:]), 1, 0)

    out = []
    for span in range(spans):
        start, stop = span * per_span * block, (span + 1) * per_span * block
        keys = (k[:, :stop], None if k_shared is None else k_shared[:, :stop],
                v[:, :stop])

        def step(first, xs, keys=keys):
            q_block, q_shared_block = xs
            return first + block, attend(q_block, q_shared_block, keys[0],
                                         keys[1], keys[2], first, scale, window)

        xs = (in_blocks(q, start, stop),
              None if q_shared is None else in_blocks(q_shared, start, stop))
        _, done = jax.lax.scan(step, jnp.int32(start), xs)  # [n, B, block, H, Dv]
        out.append(jnp.moveaxis(done, 0, 1).reshape(
            (batch, per_span * block) + done.shape[3:]))
    return jnp.concatenate(out, axis=1)
