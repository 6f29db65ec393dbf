"""The kernels of ``chip_smoke.py``'s path, compiled by the chip's own
compiler for a DESCRIBED ``v5e:2x2`` at the flagship widths (the r05
"large" row: B=8, T=2048, 8 heads × 128, d=1024, V=32768).

Interpret-mode parity tests cannot see what Mosaic refuses — a tile that
outgrows scoped VMEM, a slice off the native tiling — and a chip run costs
chip time. These compiles cost ~2 s each and no chip: nothing runs, so they
say nothing about results or speed; they only guard that every later PR
still hands the compiler kernels it accepts.

Everything that touches the topology lives in the module-scoped fixtures
below (never at import): only one process may hold the TPU library, so only
the worker that is handed this file may load it. All of these tests stay in
this one file for the same reason.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, T, H, DH, D, V = 8, 2048, 8, 128, 1024, 32768
N = B * T
SERVE_SLOTS, SERVE_CHUNK = 8, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-device compile is written to a persistent cache but can
    # never be read back without the chip; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, structs) -> str:
    """The chip compiler's verdict on ``fn``: raises what the chip would
    refuse; returns the program text, which must hold a Pallas kernel."""
    text = jax.jit(fn).lower(*structs).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


@pytest.fixture(scope="module")
def chip(topo):
    """Compile ``fn`` for one described chip from ``(shape, dtype)``
    operands."""
    one = SingleDeviceSharding(topo.devices[0])

    def compile_for_chip(fn, *operands):
        return _compile(fn, [jax.ShapeDtypeStruct(s, dt, sharding=one)
                             for s, dt in operands])

    return compile_for_chip


def _grad_sum(fn, argnums):
    """Scalar-ised fwd+bwd of ``fn`` w.r.t. ``argnums`` (sums every
    output so each one's cotangent path is compiled)."""
    def loss(*a):
        out = fn(*a)
        leaves = jax.tree.leaves(out)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in leaves)

    return jax.value_and_grad(loss, argnums=argnums)


bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


def test_flash_attention_fwd_bwd_bf16(chip):
    from tpudml.ops.attention_kernel import flash_attention

    qkv = ((B, T, H, DH), bf16)
    chip(_grad_sum(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), (0, 1, 2)), qkv, qkv, qkv)


_FLASH_KERNEL = r"\bflash_(?:fwd_resident_rows|fwd_resident|fwd|bwd_rows|bwd_dq|bwd_dkv|bwd)\b"


# The forms of each direction, each at a shape that takes it: the training
# cell's (gpt2-medium.pretrain-1k, a pair of heads resident in VMEM, read from
# the model's own rows: one kernel a direction), the same with an odd head
# count (the rows do not split into lane blocks: each head folded) and
# starcoderbase-1b's context (too long to sit there: the forward streams K/V
# tiles, the backward is the dQ and dK/dV kernels).
@pytest.mark.parametrize("shape,kernels", [
    ((8, 1024, 16, 64), {"flash_fwd_resident_rows", "flash_bwd_rows"}),
    ((8, 1024, 15, 64), {"flash_fwd_resident", "flash_bwd"}),
    ((1, 8192, 16, 128), {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
], ids=["gpt2-medium-rows", "odd_heads-folded", "long_head128-two_kernels"])
def test_flash_backward_form_on_the_chip(chip, shape, kernels):
    from tpudml.ops.attention_kernel import flash_attention

    qkv = (shape, bf16)
    text = chip(_grad_sum(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), (0, 1, 2)), qkv, qkv, qkv)
    # whole names: the frame table also holds the function ``_flash_fwd``
    assert set(re.findall(_FLASH_KERNEL, text)) == kernels


# One attention layer as the model runs it: three projections of the residual
# stream, causal flash attention, the out projection; forward and backward.
# Where H·D splits into lane blocks of whole heads the kernels read the
# projections' own [B, T, H·D] rows: no transpose, no pad of a 64-wide minor
# dimension to 128 lanes and no [B·H, T, 1] column (128-fold in memory) exists
# around them; at PR 42 the first shape held eight such copies and a column.
@pytest.mark.parametrize("shape,kernels,folds", [
    ((8, 1024, 16, 64), {"flash_fwd_resident_rows", "flash_bwd_rows"}, 0),
    ((4, 2048, 8, 128), {"flash_fwd_resident_rows", "flash_bwd_rows"}, 0),
    ((8, 1024, 15, 64), {"flash_fwd_resident", "flash_bwd"}, 8),
], ids=["gpt2-medium", "head128", "odd_heads-folded"])
def test_attention_layer_reads_the_projections_rows(chip, shape, kernels, folds):
    from tpudml.ops.attention_kernel import flash_attention

    b, t, h, d = shape

    def layer(x, wq, wk, wv, wo):
        q, k, v = ((x @ w).reshape(b, t, h, d) for w in (wq, wk, wv))
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return o.reshape(b, t, h * d) @ wo

    w = ((h * d, h * d), bf16)
    text = chip(_grad_sum(layer, (0, 1, 2, 3, 4)), ((b, t, h * d), bf16), w, w, w, w)
    assert set(re.findall(_FLASH_KERNEL, text)) == kernels
    # a fold moves all of q, k, v, o or a gradient (bf16); the lse or Δ
    # column is [B·H, T, 1] float32
    relaid = [n for n in _copied_bytes(text, "copy|transpose") if n >= 2 * b * t * h * d]
    columns = re.findall(rf"f32\[{b * h},{t},1\]", text)
    assert (len(relaid), len(columns)) == (folds, 0), (relaid, columns)


def test_fused_step_holds_a_repeated_layer_once(topo, monkeypatch):
    """The fused LM step is compiled with identical fusions deduplicated (one
    copy of a layer's code down the stack): the chip's compiler takes the
    option ``tpudml.train`` hands it on a TPU, and the program's code is a
    fraction of what it is without (gpt2-medium's step: 36 MB for 320, which
    is what fits a persistent compile cache)."""
    from tpudml import train
    from tpudml.core.prng import seed_key
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer

    assert train._one_copy_of_a_repeated_layer() is None  # the CPU's compiler has no such option
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # kernels, and the option
    options = train._one_copy_of_a_repeated_layer()
    model = TransformerLM(vocab_size=1024, embed_dim=256, num_heads=4, num_layers=6,
                          max_len=256, impl="flash", fused_ln=True)
    opt = make_optimizer("adam", 1e-3)
    body = train.make_lm_fused_train_step_body(model, opt)
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda k: train.TrainState.create(model, opt, k), seed_key(0)))
    tokens = jax.ShapeDtypeStruct((2, 256), i32, sharding=one)
    lowered = jax.jit(body, donate_argnums=(0,)).lower(state, tokens, tokens)
    code = [lowered.compile(compiler_options=o).memory_analysis().generated_code_size_in_bytes
            for o in (None, options)]
    assert code[1] < 0.5 * code[0]


def test_fused_add_layernorm_fwd_bwd(chip):
    from tpudml.ops.layernorm_kernel import fused_add_layernorm

    chip(_grad_sum(lambda x, r, s, b: fused_add_layernorm(
        x, r, s, b, interpret=False), (0, 1, 2, 3)),
        ((N, D), bf16), ((N, D), bf16), ((D,), f32), ((D,), f32))


# The blocks are left open: the plan's own tiles have to fit the VMEM
# ceiling the kernels state for them (``xent_kernel._vmem_params``), at the
# flagship widths and at the training cell's (gpt2-medium.pretrain-1k: a
# vocabulary that is no multiple of 128, so the last tile is masked).
@pytest.mark.parametrize("n,d,v", [(N, D, V), (8192, 1024, 50257)],
                         ids=["flagship", "gpt2-medium"])
@pytest.mark.parametrize("save_s", [True, False], ids=["save_s", "lean"])
def test_linear_cross_entropy_fwd_bwd(chip, save_s, n, d, v):
    from tpudml.ops.xent_kernel import linear_cross_entropy

    chip(_grad_sum(lambda x, w, y: linear_cross_entropy(
        x, w, y, interpret=False, save_s=save_s), (0, 1)),
        ((n, d), bf16), ((d, v), bf16), ((n,), i32))


@pytest.mark.parametrize("w_dtype", [f32, bf16], ids=["f32", "bf16"])
def test_fused_decode_head(chip, w_dtype):
    from tpudml.ops.decode_head import fused_decode_head

    chip(lambda x, w, b: fused_decode_head(x, w, b, interpret=False),
         ((SERVE_SLOTS, D), w_dtype), ((D, V), w_dtype), ((V,), w_dtype))


def test_fused_decode_head_int8(chip):
    from tpudml.ops.decode_head import fused_decode_head_int8

    chip(lambda x, wq, s: fused_decode_head_int8(x, wq, s, interpret=False),
         ((SERVE_SLOTS, D), f32), ((D, V), i8), ((V,), f32))


def test_grouped_dw(chip):
    from tpudml.ops.moe_kernel import grouped_dw

    chip(lambda x, g, gs: grouped_dw(x, g, gs, interpret=False),
         ((N, D), bf16), ((N, 4 * D), bf16), ((8,), i32))


def test_fused_attn_junction_fwd_bwd(chip):
    from tpudml.ops.junction_kernel import fused_attn_junction

    qkv = ((B, T, H, DH), bf16)
    chip(_grad_sum(lambda q, k, v, r, wo, bo, s, b: fused_attn_junction(
        q, k, v, r, wo, bo, s, b, interpret=False), tuple(range(8))),
        qkv, qkv, qkv, ((B, T, D), bf16), ((D, D), bf16), ((D,), bf16),
        ((D,), f32), ((D,), f32))


@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
def test_serving_chunk_window_flash(chip, dtype):
    """Chunked prefill's window attention at a non-zero chunk index: one
    SERVE_CHUNK-token chunk at global offset 3·chunk over its [0, 4·chunk)
    window (task6_serve builds an f32 model; bf16 is the trained one)."""
    from tpudml.nn.attention import _chunk_flash_window

    start = 3 * SERVE_CHUNK
    chip(lambda q, k, v: _chunk_flash_window(q, k, v, start),
         ((1, SERVE_CHUNK, H, DH), dtype),
         ((1, start + SERVE_CHUNK, H, DH), dtype),
         ((1, start + SERVE_CHUNK, H, DH), dtype))


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4}


def _copied_bytes(text: str, ops: str = "copy") -> list[int]:
    """Bytes of the result of every ``copy`` (or other ``ops``, a regex) in a
    compiled program; a ``copy-start`` / ``-done`` pair, the compiler's
    prefetch of an operand, is none."""
    import math
    import re

    return [math.prod(int(d) for d in dims.split(",") if d) * _ITEMSIZE[dt]
            for dt, dims in re.findall(
                rf"= (\w+)\[([\d,]*)\]\{{[^}}]*\}} (?:{ops})\(", text)]


@pytest.fixture
def on_chip_kernel(monkeypatch):
    """``jax.default_backend()`` is the CPU during a described compile: put
    the decode-attention kernel's dispatch where a TPU would."""
    from tpudml.ops import decode_attn

    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: False)


def _entry_ops(text: str) -> int:
    """Operations of the entry computation: what the device runs a step,
    fusions and kernels counted once each."""
    entry = text[text.index("\nENTRY "):]
    return sum(" = " in line for line in entry.splitlines())


def _serve_code_step(topo, kind, layers, in_flight=False):
    """The engine's decode step at the serving cell's cache (64 slots x
    8192 rows, multi-query 16 x 128, donated), compiled: (program text,
    one layer's K). ``in_flight``: as the run loop calls it, the tokens
    taken from the step before on the device (``_with_device_tokens``)."""
    from tpudml.models import TransformerLM
    from tpudml.serve.engine import _with_device_tokens, make_decode_step

    slots, rows = 64, 8192
    model = TransformerLM(vocab_size=1024, embed_dim=2048, num_heads=16,
                          num_layers=layers, max_len=rows, rope=False,
                          num_kv_heads=1, impl="flash", dtype=bf16,
                          compute_dtype=bf16)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(
        lambda: model.init_decode_cache(slots, rows, kind))
    ints = jax.ShapeDtypeStruct((slots,), i32, sharding=one)
    step, state = make_decode_step(model), (ints, ints)
    if in_flight:
        step = _with_device_tokens(step, slots, stateful=False)
        state = (ints, jax.ShapeDtypeStruct((4, slots), i32, sharding=one))
    text = step.lower(
        described(params), described(caches), *state).compile().as_text()
    return text, caches[0].k


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_serve_decode_step_writes_rows_in_place(topo, kind, on_chip_kernel):
    """Two layers are enough: the step's K/V rows go in by scatters — no
    ``while`` over the slots (64 passes a tensor before PR 29) and no
    ``copy`` as large as one layer's K. (bf16 reads with the kernel, int8
    with the einsum: serve/cache.py:decode_kernel.)"""
    text, k = _serve_code_step(topo, kind, layers=2)
    assert " scatter(" in text and " while(" not in text
    assert max(_copied_bytes(text)) < k.size * k.dtype.itemsize
    assert ("decode_attn" in text) == (kind == "bf16")


def test_serve_step_kept_in_flight_is_the_same_step_behind_a_select(
        topo, on_chip_kernel):
    """What the run loop dispatches (the previous step's tokens still on
    the device, the host's word in one [4, B] array) is the maker's step
    plus the select: the kernel a layer, the cache written in place
    (donated through the wrapper), a handful of operations more."""
    plain, k = _serve_code_step(topo, "bf16", layers=2)
    text, _ = _serve_code_step(topo, "bf16", layers=2, in_flight=True)
    assert text.count("decode_attn") == plain.count("decode_attn") > 0
    assert " scatter(" in text and " while(" not in text
    assert max(_copied_bytes(text)) < k.size * k.dtype.itemsize
    assert "input_output_alias" in text
    assert _entry_ops(text) <= _entry_ops(plain) + 4


def test_serve_decode_step_reads_the_cache_once_in_place(topo, monkeypatch):
    """The serving cell's whole decode step, 24 layers: one ``decode_attn``
    kernel a layer on the cache buffers as stored — nothing as large as a
    layer's K is copied, converted or broadcast to the 16 query heads on
    its way in — and the step is no more device operations than the einsum
    step it replaces (PERF.md §6, PR 31)."""
    import re

    from tpudml.ops import decode_attn

    einsum, k = _serve_code_step(topo, "bf16", layers=24)
    assert "decode_attn" not in einsum
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: False)
    text, _ = _serve_code_step(topo, "bf16", layers=24)
    calls = re.findall(r" custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert sum("decode_attn" in c for c in calls) == 24
    assert max(_copied_bytes(text)) < k.size * k.dtype.itemsize
    for dims in ("64,8192,1,128", "64,8192,16,128", "64,16,8192,128",
                 "64,8192,128"):
        assert not re.search(
            rf"= \w+\[{dims}\]\{{[^}}]*\}} (convert|broadcast)\(", text), dims
    assert _entry_ops(text) <= _entry_ops(einsum)


def test_pattern_model_decode_step_copies_no_weights_and_no_state(
        topo, on_chip_kernel):
    """The pattern model's decode step at the published widths (one layer of
    each kind, 64 of 128 experts held, 128 slots x 4096 rows, donated): the
    held experts' weights go into their two matmuls as they are stored — a
    grouped matmul over sorted rows transposed all 640 MB of ``up`` every
    step, the chip laying out [64, 2688, 1856] with 2688 minor-most
    (PERF.md §6, PR 30) — and the recurrent state is updated in place."""
    from tpudml.models import HybridLM
    from tpudml.serve.engine import make_stateful_decode_step

    slots, rows = 128, 4096
    model = HybridLM(
        vocab_size=1024, pattern="ME*", embed_dim=2688, num_heads=32,
        num_kv_heads=2, head_dim=128, impl="flash", mamba_heads=64,
        mamba_head_dim=64, n_groups=8, state_size=128, chunk_size=128,
        num_experts=128, top_k=6, expert_dim=1856, shared_dim=3712,
        routed_scale=2.5, held=(0, 64), dtype=bf16)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(lambda: model.init_decode_cache(slots, rows, "bf16"))
    state = jax.ShapeDtypeStruct((3, slots), i32, sharding=one)
    text = make_stateful_decode_step(model).lower(
        described(params), described(caches), state).compile().as_text()
    assert " while(" not in text
    up = params["layer1"]["mixer"]["experts"]["up"]
    assert max(_copied_bytes(text)) < up.size * up.dtype.itemsize / 2
    # Nor is the K/V cache: the decode-attention kernel reads [128, 4096,
    # 2, 128] as stored, where the grouped einsum wanted [B, Hkv, L, D] and
    # copied K and V of each attention layer a step (PERF.md §6, PR 31).
    k = caches[2].k
    assert k.shape == (128, 4096, 2, 128) and "decode_attn" in text
    assert max(_copied_bytes(text)) < k.size * k.dtype.itemsize
    # The state is not among the copies, as stored or as the scan sees it.
    import re

    assert caches[0].ssm.shape == (128, 64, 64, 128)
    for dims in ("128,64,64,128", "128,8,8,64,128"):
        assert not re.search(rf"= f32\[{dims}\]\{{[^}}]*\}} copy\(", text)


def _window_full_model():
    """One full, one window and one expert layer at MiMo-V2.5's published
    widths (16 of 256 experts held), 128 slots x 8192 rows."""
    from tpudml.models import HybridLM

    return HybridLM(
        vocab_size=1024, pattern="FWE", embed_dim=4096, num_heads=64, head_dim=192,
        v_head_dim=128, rotary_dim=64, value_scale=0.707, full_kv_heads=4,
        window=128, window_kv_heads=8, num_experts=256, top_k=8, expert_dim=2048,
        shared_dim=0, gated_experts=True, held=(0, 16), dtype=bf16)


def _described(tree, one):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)


def test_window_and_full_layers_decode_step_takes_both_fast_paths(topo, on_chip_kernel):
    """A 192-wide key stored in 256 lanes beside a 128-wide value: the step
    writes by scatters, reads each cache with the kernel under its own name,
    and copies neither a cache nor a weight — a 192-wide head made the chip's
    compiler transpose the 100 MB q kernel every step until `_project`'s
    barrier (PERF.md §6, PR 37)."""
    from tpudml.serve.engine import make_stateful_decode_step

    model, slots, rows = _window_full_model(), 128, 8192
    assert model.cache_forms(rows, "bf16") == (True, True)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(lambda: model.init_decode_cache(slots, rows, "bf16"))
    assert caches[0].k.shape == (128, 8192, 4, 256) and caches[1].v.shape == (128, 128, 8, 128)
    state = jax.ShapeDtypeStruct((3, slots), i32, sharding=one)
    text = make_stateful_decode_step(model).lower(
        _described(params, one), _described(caches, one), state).compile().as_text()
    assert " scatter(" in text and " while(" not in text
    calls = re.findall(r" custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert sum("decode_attn_window" in c for c in calls) == 1 and len(calls) == 2
    q_kernel = params["layer0"]["mixer"]["q"]["kernel"]
    assert max(_copied_bytes(text)) < q_kernel.size * q_kernel.dtype.itemsize / 4


def test_window_and_full_layers_prefill_chunk_leaves_the_cache_in_place(topo):
    """The last chunk of a 4096-token prompt: the full layer's scores contract
    over the key's stored 256 lanes, so nothing as large as the ring, let alone
    the 2 GB cache, is re-laid (slicing 192 of the 256 lanes made the compiler
    transpose the whole cache there and back; PERF.md §6, PR 37)."""
    model, slots, rows = _window_full_model(), 128, 8192
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(lambda: model.init_decode_cache(slots, rows, "bf16"))
    chunk = jax.ShapeDtypeStruct((1, 512), i32, sharding=one)
    scalar = jax.ShapeDtypeStruct((), i32, sharding=one)
    text = jax.jit(
        lambda p, c, ch, slot, n: model.apply_prefill(p, c, ch, slot, 3584, n),
        donate_argnums=(1,)).lower(_described(params, one), _described(caches, one),
                                   chunk, scalar, scalar).compile().as_text()
    ring = caches[1].k
    assert max(_copied_bytes(text)) < ring.size * ring.dtype.itemsize


def test_latent_layers_decode_step_reads_the_latent_cache_once_where_it_lies(
        topo, on_chip_kernel):
    """DeepSeek-V2's widths, one latent + dense and one latent + expert layer:
    the step writes each latent cache by a scatter and reads it with
    ``decode_attn_latent``, ONE cache operand a call (key whole, value its first
    512 lanes); nothing as large as a layer's W_UKV is copied, so neither the
    cache (donated, 2.7 GB a layer) nor a weight is."""
    from tpudml.models import HybridLM
    from tpudml.serve.engine import make_stateful_decode_step

    model = HybridLM(
        vocab_size=1024, pattern="LDLE", embed_dim=5120, num_heads=128, q_rank=1536,
        kv_rank=512, nope_dim=128, rope_dim=64, v_head_dim=128,
        yarn=(40, 4096, 32, 1, 0.707, 0.707), dense_dim=1024, num_experts=160, top_k=6,
        expert_dim=1536, shared_dim=3072, gated_experts=True, routed_scale=16.0,
        norm_topk=False, held=(0, 4), moe_scoring="softmax", moe_groups=(8, 3), eps=1e-6,
        dtype=bf16)
    slots, rows = 256, 4096
    assert model.cache_forms(rows, "bf16") == (True, True)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(lambda: model.init_decode_cache(slots, rows, "bf16"))
    assert caches[0].rows.shape == (256, 4096, 640) and caches[1] is None
    state = jax.ShapeDtypeStruct((3, slots), i32, sharding=one)
    text = make_stateful_decode_step(model).lower(
        _described(params, one), _described(caches, one), state).compile().as_text()
    assert " scatter(" in text and " while(" not in text
    calls = re.findall(r" custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == 2 and all("decode_attn_latent" in c for c in calls)
    assert all(c.count("bf16[256,4096,640]") == 1 for c in calls)  # read once: one operand
    kv_up = params["layer0"]["mixer"]["kv_up"]["kernel"]
    assert kv_up.shape == (128, 512, 256)  # W_UK and W_UV are its halves, held once
    assert max(_copied_bytes(text)) <= kv_up.size * kv_up.dtype.itemsize


def test_shared_cache_model_decode_step_copies_neither_the_table_nor_a_cache(
        topo, on_chip_kernel):
    """Phi-4-mini-flash-reasoning's widths, one layer of each kind, all
    200,064 vocabulary rows, 64 slots x 4096 rows: the tied head contracts the
    [200064, 2560] table where it lies (no transposed 1 GB copy a step), and
    the pair-row caches, stored flat, are written by scatters and read by the
    kernel under its three names with nothing as large as a ring re-laid —
    stored [B, L, 10, 128] the chip keeps them L-minor and the kernel's
    layout costs two copies of every cache a step (PERF.md §6, PR 39)."""
    from tpudml.models import HybridLM
    from tpudml.serve.engine import make_stateful_decode_step

    slots, rows = 64, 4096
    model = HybridLM(
        vocab_size=200064, pattern="SDWDSDFDGDXD", embed_dim=2560, num_heads=40, head_dim=64,
        attn_bias=True, differential=True, window=512, dense_dim=10240, ssm_inner=5120,
        dt_rank=160, state_size=16, conv_kernel=4, norm="layer", tied=True, dtype=bf16)
    assert model.cache_forms(rows, "bf16") == (True, True) and model.prefill_entries == 7
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    caches = jax.eval_shape(lambda: model.init_decode_cache(slots, rows, "bf16"))
    assert caches[6].k.shape == (64, 4096 * 10, 1, 128) and caches[10] is None
    assert caches[2].v.shape == (64, 512 * 10, 1, 128) and caches[0].ssm.shape == (64, 1, 16, 5120)
    state = jax.ShapeDtypeStruct((3, slots), i32, sharding=one)
    text = make_stateful_decode_step(model).lower(
        _described(params, one), _described(caches, one), state).compile().as_text()
    assert " scatter(" in text and " while(" not in text
    calls = re.findall(r" custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    names = [re.search(r"/(decode_attn\w*)/pallas_call", c).group(1) for c in calls]
    assert sorted(names) == ["decode_attn", "decode_attn_shared", "decode_attn_window"]
    ring = caches[2].k
    assert max(_copied_bytes(text)) < ring.size * ring.dtype.itemsize / 8
    assert not re.search(r"\[(200064,2560|2560,200064)\]\{[^}]*\} (copy|transpose)\(", text)


# ------------------------------------------------- across the four chips
# What exists only on a mesh: the SPMD partitioner refuses a bare Mosaic
# kernel, so under the GSPMD engines the kernels run per shard; and the
# vocab-sharded head merges per-shard statistics with collectives.


@pytest.fixture(scope="module")
def mesh4(topo):
    """Compile ``fn(mesh, *operands)`` for the four described chips as one
    ``axis`` mesh from ``(shape, dtype, PartitionSpec)`` operands."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    def compile_on_mesh(fn, axis, *operands):
        mesh = Mesh(np.array(topo.devices), (axis,))
        return _compile(
            lambda *a: fn(mesh, *a),
            [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, spec))
             for s, dt, spec in operands])

    return compile_on_mesh


@pytest.mark.parametrize("axis,batch,head", [("data", "data", None),
                                             ("model", None, "model")],
                         ids=["fsdp_layout", "tp_layout"])
def test_trunk_kernels_per_shard_under_gspmd(mesh4, axis, batch, head):
    """flash attention + the add+LN junction inside a GSPMD-partitioned
    jit, fwd+bwd, under the layout the FSDP / TP engines declare."""
    from jax.sharding import PartitionSpec as P

    from tpudml.ops.attention_kernel import flash_attention
    from tpudml.ops.layernorm_kernel import fused_add_layernorm
    from tpudml.parallel.sharding import kernel_layout

    def trunk(mesh, q, k, v, r, s, b):
        def loss(q, k, v, r, s, b):
            with kernel_layout(mesh, batch=batch, head=head):
                o = flash_attention(q, k, v, causal=True, interpret=False)
                o = o.reshape(r.shape)
                stream, y = fused_add_layernorm(r, o, s, b, interpret=False)
            return jnp.sum(y.astype(f32)) + jnp.sum(stream.astype(f32))

        return jax.grad(loss, argnums=tuple(range(6)))(q, k, v, r, s, b)

    qkv = ((4, T, H, DH), bf16, P(batch, None, head, None))
    mesh4(trunk, axis, qkv, qkv, qkv, ((4, T, D), bf16, P(batch)),
          ((D,), f32, P()), ((D,), f32, P()))


@pytest.mark.parametrize("n,v", [(4 * T, V), (2048, 51200)],
                         ids=["flagship", "2k_rows-12800_a_shard"])
def test_sharded_linear_cross_entropy_fwd_bwd(mesh4, n, v):
    """The vocab-sharded fused head (TP): per-shard kernel + lse merge,
    at the tiles the plan gives a shard's rows and local vocabulary."""
    from jax.sharding import PartitionSpec as P

    from tpudml.ops.xent_kernel import sharded_linear_cross_entropy
    from tpudml.parallel.sharding import shard_map_fn

    def head(mesh, x, w, y):
        region = shard_map_fn(
            lambda x, w, y: sharded_linear_cross_entropy(
                x, w, y, axis_name="model", interpret=False),
            mesh, in_specs=(P(), P(None, "model"), P()), out_specs=P())
        return jax.value_and_grad(region, argnums=(0, 1))(x, w, y)

    mesh4(head, "model", ((n, D), bf16, P()),
          ((D, v), bf16, P(None, "model")), ((n,), i32, P()))
