"""The tiled warp of ``ops/augment.py`` compiled at its real sizes for
the chip it runs on, with no chip attached: what the TPU's compiler
would refuse (a shape it cannot lay out, more memory than the budget
meant) fails here and costs no chip time.  Nothing runs, so nothing
here is a time or a pixel: ``tests/test_augment_warp_dense.py`` has the
pixels, PERF.md the times.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fast_autoaugment_tpu.ops import augment as A


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e; the library is loaded here, by the one worker
    that runs this file, and never while a module is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache and cannot be read back without the chip."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


# a step's batch at 224 px (`resnet50_imagenet_train`) and EfficientNet's largest conf
@pytest.mark.parametrize("n,size", [(128, 224), (64, 380)])
def test_tiled_warp_compiles_for_a_v5e_inside_its_budget(one_chip, no_compile_cache, n, size):
    assert A._warp_tiling(size, size) is not None
    imgs = jax.ShapeDtypeStruct((n, size, size, 3), jnp.float32, sharding=one_chip)
    mats = jax.ShapeDtypeStruct((n, 2, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).compile()
    # the chunks' intermediates, the output and the tiles' indices beside them
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < A._DENSE_WARP_BUDGET_BYTES + 3 * n * size * size * 3 * 4
    text = compiled.as_text()
    assert "conditional" in text  # the run-time choice is a branch, not a select
    assert A._warp_bytes_an_image(size, size, 3) * n > A._DENSE_WARP_BUDGET_BYTES
    assert "while" in text  # so the batch runs in chunks


# --- the token model's kernels at the timed sizes (PR 35, PR 39), in this file
# because one worker may load the TPU's library and this file's fixture does


@pytest.mark.parametrize("ambient", ["default", "highest"])
def test_fused_kda_kernels_compile_for_a_v5e(one_chip, no_compile_cache,
                                             monkeypatch, ambient):
    """One KDA layer of `kimi_linear_48b_a3b_train`: 8,192 tokens, 32
    heads of 128 side by side as the projections leave them, through the
    kernels as the chip gets them (the backend here is the CPU, which
    would interpret them), with bfloat16 operands and under ``highest``.  Forward and backward are one Mosaic kernel
    each, and beside the inputs' gradients HBM holds the kept states (268
    MB) and little else."""
    from fast_autoaugment_tpu.ops import kda

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    args = (shape(1, 8192, 32 * 128),) * 4 + (shape(1, 8192, 32),)

    def scalar(*a):
        out, state = kda.chunk_kda(*a)
        return jnp.sum(out) + jnp.sum(state)

    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "kda_forward" in text and "kda_backward" in text
    assert text.count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_a_kda_mixer_moves_no_projection_between_tilings_on_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """One KDA mixer of `kimi_linear_48b_a3b_train` under ``nn.remat``,
    forward and backward at 8,192 tokens: the program XLA makes of it
    holds no ``copy``, ``reshape`` or ``transpose`` of an array as large
    as a projection's output (8,192 x 4,096 float32: 134 MB) — what stood
    some 25 times a layer round kernels that read ``[B, T * H, K]`` rows
    (PR 39 to PR 47).  Only the block's own input (8,192 x 2,304) is
    still copied."""
    from flax import linen as nn

    from fast_autoaugment_tpu.models.kimi_linear import KDAMixer
    from fast_autoaugment_tpu.models.token_blocks import remat_block
    from fast_autoaugment_tpu.ops import kda

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x + KDAMixer(32, 128, 4, 1e-5, name="kda")(x)

    layer = remat_block(Layer)()
    x = jax.ShapeDtypeStruct((1, 8192, 2304), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    compiled = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer.apply(p, x) ** 2), argnums=(0, 1))).lower(
            params, x).compile()
    text = compiled.as_text()
    assert "kda_forward" in text and "kda_backward" in text
    moved = re.findall(r"= f32\[([\d,]+)\][^ ]* (copy|reshape|transpose)\(", text)
    sizes = [np.prod([int(d) for d in dims.split(",")]) for dims, _ in moved]
    assert moved and max(sizes) < 8192 * 4096, [m for m in moved if "4096" in m[0]]


@pytest.mark.parametrize("parts", [dict(window=2048, rope_theta=10000.0), {}],
                         ids=["window_rotary", "full"])
def test_a_gqa_mixer_moves_no_projection_between_tilings_on_a_v5e(
        one_chip, no_compile_cache, monkeypatch, parts):
    """One grouped-query mixer of `trinity_mini_train` (32 heads of 128 on
    4 key-value heads, a norm a head on queries and keys, gated; a window
    layer's span with rotary, and the full layer) under ``remat_block``,
    forward and backward at 16,384 tokens: each attention kernel once (the
    kept output and log-sum-exp), the norm a head and the rotation of
    queries and of keys one kernel a pass (``ops/headnorm.py``: forward,
    again under the remat, backward), and the program XLA makes of it holds no
    ``copy``, ``reshape`` or ``transpose`` of an array as large as ``q``
    (16,384 x 4,096: 268 MB in float32) anywhere, and no ``broadcast`` of
    one as an operation of its own — what stood some twenty times a layer
    round kernels handed ``[B, T, H, D]`` and the key-value heads repeated
    (PR 46 to PR 49)."""
    from flax import linen as nn

    from fast_autoaugment_tpu.models.token_blocks import GQAMixer, remat_block
    from fast_autoaugment_tpu.ops import kda

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x + GQAMixer(32, 4, 128, qk_norm_eps=1e-5, gated=True, name="attn",
                                **parts)(x)

    layer = remat_block(Layer)()
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    compiled = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer.apply(p, x) ** 2), argnums=(0, 1))).lower(
            params, x).compile()
    text = compiled.as_text()
    kernels = {name: len(re.findall(rf"%{name}[.\d]* = ", text)) for name in (
        "mla_attention_forward", "mla_attention_backward", "head_norm_rotate_forward",
        "head_norm_rotate_backward")}
    assert kernels == {"mla_attention_forward": 1, "mla_attention_backward": 1,
                       "head_norm_rotate_forward": 4, "head_norm_rotate_backward": 2}, kernels
    assert text.count("tpu_custom_call") == 8

    def largest(moved):
        return max([np.prod([int(d) for d in dims.split(",")]) for dims in moved], default=0)

    anywhere = re.findall(r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(", text)
    assert anywhere and largest(anywhere) < 16384 * 4096, sorted(set(anywhere))
    entry = text[text.index("\nENTRY "):]
    alone = re.findall(r"= \w+\[([\d,]+)\]\S* broadcast\(", entry)
    assert largest(alone) < 16384 * 4096, sorted(set(alone))


@pytest.mark.parametrize("ambient", ["default", "highest"])
def test_an_mla_mixer_moves_no_projection_between_tilings_on_a_v5e(
        one_chip, no_compile_cache, monkeypatch, ambient):
    """One latent-attention mixer of `glm47_flash_train` (20 heads of 192 +
    64 on values of 256, a low-rank query, rotary) under ``remat_block``,
    forward and backward at 8,192 tokens, at the chip's default precision
    and under ``highest``: each attention kernel once, the row passes that
    turn the queries' rotary lanes and round them and that lay the shared key
    part into `k` (``ops/mlarows.py``) once a pass each (forward, again under
    the remat) and their transpose once, and the program XLA makes of it
    holds no ``copy``, ``reshape`` or ``transpose`` of an array as large as a
    projection's output (8,192 x 5,120: 168 MB in float32) anywhere, and no
    ``broadcast``, ``pad``, ``slice`` or ``concatenate`` of one as an
    operation of its own — what stood some thirty times a layer round
    kernels handed ``[B, T, H, D]`` (PR 41 to PR 51)."""
    from flax import linen as nn

    from fast_autoaugment_tpu.models.token_blocks import MLAMixer, remat_block
    from fast_autoaugment_tpu.ops import kda

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x + MLAMixer(20, 192, 64, 256, 512, 1e-5, q_rank=768, rope_theta=1e6,
                                name="mla")(x)

    layer = remat_block(Layer)()
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(
            lambda p, x: jnp.sum(layer.apply(p, x) ** 2), argnums=(0, 1))).lower(
                params, x).compile()
    text = compiled.as_text()
    kernels = {name: len(re.findall(rf"%{name}[.\d]* = ", text)) for name in (
        "mla_attention_forward", "mla_attention_backward", "mla_rows_turn", "mla_rows_lay",
        "mla_rows_unlay")}
    assert kernels == {"mla_attention_forward": 1, "mla_attention_backward": 1,
                       "mla_rows_turn": 2, "mla_rows_lay": 2, "mla_rows_unlay": 1}, kernels
    assert text.count("tpu_custom_call") == 7

    def largest(moved):
        return max([np.prod([int(d) for d in dims.split(",")]) for dims in moved], default=0)

    anywhere = re.findall(r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(", text)
    assert anywhere and largest(anywhere) < 8192 * 5120, sorted(set(anywhere))
    entry = text[text.index("\nENTRY "):]
    alone = re.findall(r"= \w+\[([\d,]+)\]\S* (?:broadcast|pad|slice|concatenate)\(", entry)
    assert largest(alone) < 8192 * 5120, sorted(set(alone))


@pytest.mark.parametrize("ambient", ["default", "highest"])
@pytest.mark.parametrize("heads, dim, shared, vdim", [(32, 128, 64, 128), (20, 192, 64, 256)],
                         ids=["kimi_linear_48b_a3b_train", "glm47_flash_train"])
def test_fused_attention_kernels_compile_for_a_v5e(one_chip, no_compile_cache, monkeypatch,
                                                   heads, dim, shared, vdim, ambient):
    """One latent-attention core of each token cell at 8,192 tokens,
    through the kernels as the chip gets them, with bfloat16 operands and
    under ``highest``.  Forward and backward are one Mosaic kernel each.
    Whole, the scores of 32 heads are 8.6 GB in float32 and the XLA form
    kept up to a quarter of that; beside the kernels HBM holds the
    operands as the products take them (the shared key part copied to
    every head, padded to whole lanes: 256 a head in both cells), their
    gradients and the rows' sums, and nothing the size of a tile's scores
    times the sequence."""
    from fast_autoaugment_tpu.ops import kda
    from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    args = (shape(1, 8192, heads, dim), shape(1, 8192, heads, dim),
            shape(1, 8192, heads, vdim), shape(1, 8192, heads, shared),
            shape(1, 8192, shared))

    def scalar(q, k, v, q_pe, k_pe):
        return jnp.sum(blocked_causal_attention(
            q, k, v, q_shared=q_pe, k_shared=k_pe, scale=(dim + shared) ** -0.5))

    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "mla_attention_forward" in text and "mla_attention_backward" in text
    assert text.count("tpu_custom_call") == 2
    # q and k at 256 a head and v, as operands (float32 under `highest`)
    # and as gradients: 1.2 GB at the most, 32 heads under `highest`
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9


def _assert_the_sum_is_combined_in_place(compiled, width):
    """Of a compiled ``held_experts`` and its backward pass over 8,192
    tokens of `width`: both loops add their blocks through the kernel, no
    scatter over the whole sum is left, and inside a loop's body nothing
    but the kernel (and the body's own argument) makes an array the size
    of the sum — no copy in front of the aliased operand."""
    text = compiled.as_text()
    assert text.count("moe_combine") >= 2 and text.count("tpu_custom_call") == 2
    whole = rf"f32\[8192,(?:1,)?{width}\]"
    assert not re.search(rf"= {whole}\S* scatter\(", text)
    computations, lines = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            lines = computations.setdefault(
                re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1), [])
        elif lines is not None:
            lines.append(line)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    assert len(bodies) >= 2
    makers = {m.group(1) for body in bodies for line in computations[body]
              if (m := re.search(rf"= {whole}\S* ([\w-]+)\(", line))}
    # ``broadcast``: this test's cotangent, the gradient of a plain sum
    assert "custom-call" in makers
    assert makers <= {"get-tuple-element", "custom-call", "broadcast"}, makers


@pytest.mark.parametrize("width, top_k, expert_width", [(2304, 8, 1024), (2048, 4, 1536)],
                         ids=["kimi_linear_48b_a3b_train", "glm47_flash_train"])
def test_grouped_experts_and_their_backward_compile_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch, width, top_k, expert_width):
    """One expert layer's held share of `kimi_linear_48b_a3b_train`: 8,192
    tokens, top-8 of 256, 8 experts of 2,304 x 1,024 held; and of
    `glm47_flash_train`: top-4 of 64, 8 experts of 2,048 x 1,536.  Both
    loops over the blocks the routing filled (forward, and the backward
    pass ``ops/moe.py`` writes itself) stay loops, and nothing the size of
    the worst case (every token through every held expert: 65,536 rows) is
    written out."""
    from fast_autoaugment_tpu.ops import kda, moe

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, chosen, weights = (shape((8192, width)), shape((8192, top_k), jnp.int32),
                          shape((8192, top_k)))
    gate, up, down = (shape((8, width, expert_width)), shape((8, width, expert_width)),
                      shape((8, expert_width, width)))

    def scalar(x, weights, gate, up, down, chosen):
        return jnp.sum(moe.held_experts(x, chosen, weights, gate, up, down, first=0))

    compiled = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4))).lower(
        x, weights, gate, up, down, chosen).compile()
    assert compiled.as_text().count(" while(") >= 2
    # inputs, their gradients and one block's rows: far under the 604 MB
    # that 65,536 gathered rows alone would take
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
    _assert_the_sum_is_combined_in_place(compiled, width)


# --- the third token cell's shapes (PR 42): the attention kernels on repeated
# key-value heads, the scan, and the grouped loop under the two-matrix form


@pytest.mark.parametrize("ambient", ["default", "highest"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fused_attention_kernels_take_repeated_key_value_heads_and_either_dtype(
        one_chip, no_compile_cache, monkeypatch, dtype, ambient):
    """`nemotron3_nano_30b_a3b_train`'s one attention layer: 32 query
    heads of 128 at 8,192 tokens on 2 key-value heads, handed over as the 2
    they are — the kernels' index maps give a group of 16 its head, and
    nothing the size of the repeat (67 MB a tensor in float32 where 4 MB
    stand) is made — no shared key part.  A model in ``precision: bf16`` hands
    the core bfloat16 activations; under ``highest`` (the float32
    comparison's control) the kernels' products are float32 ones, which
    Mosaic refuses bfloat16 operands for — the chip refused it on PR 42's
    first controls call — so the core takes float32 in whatever comes."""
    from fast_autoaugment_tpu.ops import kda
    from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scalar(q, k, v):
        return jnp.sum(blocked_causal_attention(q, k, v, scale=128 ** -0.5))

    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2))).lower(
            shape(1, 8192, 32, 128), shape(1, 8192, 2, 128),
            shape(1, 8192, 2, 128)).compile()
    text = compiled.as_text()
    assert "mla_attention_forward" in text and "mla_attention_backward" in text
    assert text.count("tpu_custom_call") == 2
    assert not re.search(r"\[8192,(2,16|32),128\]\S* broadcast\(", text)
    # q as an operand and its gradient, the output and its cotangent, float32
    # under `highest`: 134 MB each
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


def _scan_gradient_compiled(one_chip, length, chunk):
    """Every argument's gradient of one Mamba-2 layer's scan at
    `nemotron3_nano_30b_a3b_train`'s widths (64 heads of 64 over a state
    of 128, 8 groups), compiled for the described chip."""
    from fast_autoaugment_tpu.ops.ssd import chunk_ssd

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    args = (shape(1, length, 64, 64), shape(1, length, 64), shape(64),
            shape(1, length, 8, 128), shape(1, length, 8, 128), shape(64))

    def scalar(*a):
        return jnp.sum(chunk_ssd(*a, chunk=chunk))

    return jax.jit(jax.grad(scalar, argnums=tuple(range(6)))).lower(*args).compile()


@pytest.mark.parametrize("ambient", ["default", "highest"])
def test_fused_ssd_kernels_compile_for_a_v5e(one_chip, no_compile_cache, monkeypatch,
                                             ambient):
    """One Mamba-2 layer's scan of `nemotron3_nano_30b_a3b_train`: 8,192
    tokens in 64 chunks of 128, through the kernels as the chip gets them
    (the backend here is the CPU, which would interpret them), with
    bfloat16 operands and under ``highest``.  Forward and backward are one
    Mosaic kernel each; beside the arguments' gradients HBM holds the kept
    start states (134 MB) and this test's own turn of ``x`` and ``y`` to
    rows and back (the mixer hands rows), and nothing the size of a
    chunk's ``[64 heads, 128, 128]`` times the chunks (268 MB each in the
    ``jnp`` form, a handful alive at once: 3 GB was its budget here)."""
    from fast_autoaugment_tpu.ops import kda

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    with jax.default_matmul_precision(ambient):
        compiled = _scan_gradient_compiled(one_chip, 8192, 128)
    text = compiled.as_text()
    assert "ssd_forward" in text and "ssd_backward" in text
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_chunked_state_space_scan_compiles_for_a_v5e_where_the_kernels_do_not_serve(
        one_chip, no_compile_cache):
    """The same widths in chunks of 64, which the kernels are not written
    for: the ``jnp`` form, forward and JAX's own backward, its chunk
    states' pass a loop and no kernel in the program."""
    compiled = _scan_gradient_compiled(one_chip, 2048, 64)
    text = compiled.as_text()
    assert " while(" in text and "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_grouped_two_matrix_experts_and_their_backward_compile_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """One expert layer's held share of `nemotron3_nano_30b_a3b_train`:
    8,192 tokens, top-6 of 128, 8 relu2 experts of 2,688 x 1,856 held:
    the same two loops under the second form (``ops/moe.py::FORMS``)."""
    from fast_autoaugment_tpu.ops import kda, moe

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, chosen, weights = shape((8192, 2688)), shape((8192, 6), jnp.int32), shape((8192, 6))
    up, down = shape((8, 2688, 1856)), shape((8, 1856, 2688))

    def scalar(x, weights, up, down, chosen):
        return jnp.sum(moe.held_experts(x, chosen, weights, up, down, first=0,
                                        form="relu2"))

    compiled = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3))).lower(
        x, weights, up, down, chosen).compile()
    assert compiled.as_text().count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    _assert_the_sum_is_combined_in_place(compiled, 2688)


# --- the device cache's batch gather at CIFAR's size (PR 37), in this file
# for the same reason


@pytest.mark.parametrize("index_shape", [(2048,), (5, 512)], ids=["batch", "stacked"])
def test_batch_taken_from_the_stored_form_copies_no_whole_cache(
        one_chip, no_compile_cache, index_shape):
    """``StoredRows.take`` on 50,000 32-px images: the chip keeps a rank-4
    ``uint8`` argument with the example axis minor-most and copied all of
    it in front of every gather (PERF.md section 6, PR 37); from rows the
    compiled program touches the whole cache in the gather alone, and
    needs no temporary of its size."""
    from fast_autoaugment_tpu.data.pipeline import StoredRows

    cache = jax.tree.map(
        lambda rows: jax.ShapeDtypeStruct(
            (50000,) + rows.shape[1:], rows.dtype, sharding=one_chip),
        StoredRows.of(np.zeros((1, 32, 32, 3), np.uint8)))
    idx = jax.ShapeDtypeStruct(index_shape, jnp.int32, sharding=one_chip)

    def first_consumer(stored, i):  # the step reads its batch as float
        return stored.take(i).astype(jnp.float32) * (1.0 / 255.0)

    compiled = jax.jit(first_consumer).lower(cache, idx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 50000 * 3072 // 4
    whole = re.compile(r"= \w+\[50000,[\d,]*\]\S* (\w[\w-]*)\(")
    makers = {m.group(1) for line in compiled.as_text().splitlines()
              if (m := whole.search(line))}
    assert makers <= {"parameter"}, makers  # nothing makes an array that size


# --- the fourth token cell's shapes (PR 46): the attention kernels under a
# key span, and without one, at 16,384 tokens


@pytest.mark.parametrize("ambient", ["default", "highest"])
@pytest.mark.parametrize("window", [2048, None, 1000], ids=["window", "full", "no_whole_tiles"])
def test_fused_attention_kernels_take_a_key_span_at_16384_tokens(
        one_chip, no_compile_cache, monkeypatch, window, ambient):
    """`trinity_mini_train`'s mixers: 32 query heads of 128 at 16,384
    tokens on 4 key-value heads as they are (a group of 8 by index map), a
    window layer's span of 2,048 keys (four tiles of 512), a full layer's
    whole past, and a span that is no whole number of tiles (two tiles at
    the band's trailing edge take the mask).  One head's whole sequence and
    a group's sums of ``dk``, ``dv`` beside it fit the kernels' VMEM at 128
    + 128 (2 x 4 x 16,384 x 384 + 4 x 16,384 x 256 = 67.1 MB of the 78.6
    the reckoning allows), so all take the fused form, under ``highest``
    too; the loops' bounds are scalars the kernels compute from the grid
    step, which Mosaic has to take, as it has to take the sums' one buffer
    and the head axis in order."""
    from fast_autoaugment_tpu.ops import kda
    from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def scalar(q, k, v):
        return jnp.sum(blocked_causal_attention(q, k, v, scale=128 ** -0.5,
                                                window=window))

    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2))).lower(
            shape(1, 16384, 32, 128), shape(1, 16384, 4, 128),
            shape(1, 16384, 4, 128)).compile()
    text = compiled.as_text()
    assert "mla_attention_forward" in text and "mla_attention_backward" in text
    assert text.count("tpu_custom_call") == 2
    # q as an operand and as a gradient, the output and its cotangent: 268
    # MB each in float32; k and v an eighth of that
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9


# --- the fifth token cell's shapes (PR 49): heads of 64, two to a 128-lane
# block of the attention kernels, at 16,384 tokens


@pytest.mark.parametrize("ambient", ["default", "highest"])
def test_fused_attention_kernels_take_paired_heads_of_64_at_16384_tokens(
        one_chip, no_compile_cache, monkeypatch, ambient):
    """`lfm2_8b_a1b_train`'s attention mixers: 32 query heads of 64 at
    16,384 tokens on 8 key-value heads, which ``ops/attention.py`` repeats
    in front of the kernels (half a block of lanes a key-value head).  A
    pair of heads is one 128-lane block of ``[1, 16384, 2048]`` as it lies
    (2 x 4 x 16,384 x 384 = 50.3 MB of the 78.6 the reckoning allows a
    pair), so the kernels take the fused form, with bfloat16 operands and
    under ``highest``: the stacked operands' masks and concatenations, the
    rows' halves and the folds are what Mosaic has to take."""
    from fast_autoaugment_tpu.ops import kda
    from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

    monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def scalar(q, k, v):
        return jnp.sum(blocked_causal_attention(q, k, v, scale=64 ** -0.5))

    with jax.default_matmul_precision(ambient):
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2))).lower(
            shape(1, 16384, 32, 64), shape(1, 16384, 8, 64),
            shape(1, 16384, 8, 64)).compile()
    text = compiled.as_text()
    assert "mla_attention_forward" in text and "mla_attention_backward" in text
    assert text.count("tpu_custom_call") == 2
    # q, k, v at 32 heads of 64 as operands and as gradients, the output and
    # its cotangent: 134 MB each in float32; no score matrix (a head's alone
    # is 1.07 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9
