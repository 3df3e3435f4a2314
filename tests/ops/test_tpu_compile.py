"""The kernels of the main path, compiled ahead of time for a v5e chip
that is described and not attached, at Llama-3-8B's widths.

Interpret mode cannot see what the chip's compiler refuses: a block
that breaks the (8, 128) tiling, an operand layout Mosaic does not
accept, an operation it cannot legalise. Before this file three of the
four kernel families of the serving path passed every interpret-mode
test and were refused here. Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU's library, and every
pytest-xdist worker imports every test file. Keep these tests in this
one file for the same reason.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# LlamaConfig.llama3_8b: 4096 wide, 32 query / 8 kv heads of 128,
# d_ff 14336, vocabulary 128256
N_HEADS, N_KV, HEAD_DIM = 32, 8, 128
# (K, N) of the decoder's projections: gate/up, down, lm_head
PROJECTIONS = [(4096, 14336), (14336, 4096), (4096, 128256)]
ROWS = [8, 512]   # a decode step's batch, a prefill bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without the chip (the next one warns
    and compiles again): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("batch,seq", [(4, 2048), (1, 8192), (1, 32768),
                                       (1, 65536)])
def test_flash_attention(one_chip, batch, seq, backward):
    """The three kernels with the tiles the rule chooses: at the Mistral
    cell's shape (whole inside Mosaic's default scope: nothing asked),
    at S = 8192 and 32768, where the streamed side is whole too and
    each kernel asks Mosaic for the VMEM that takes, and at S = 65536,
    past the rule's ceiling, where it comes in major blocks. An
    overfull VMEM fails here, without a chip."""
    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.ops.pallas.flash_attention import flash_tiles

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for kernel in ("fwd", "dq", "dkv"):
        tiles = flash_tiles(kernel, seq, HEAD_DIM, 2)
        assert (tiles.major == seq) == (seq <= 32768)
        assert (tiles.vmem_limit > 0) == (seq > 2048)
    qkv = _shape(one_chip, (batch, seq, N_HEADS, HEAD_DIM), jnp.bfloat16)
    compiled = _compile(fwd_bwd if backward else fwd, qkv, qkv, qkv)
    assert compiled.as_text().count("tpu_custom_call") >= (
        3 if backward else 1)


@pytest.mark.parametrize("page", [16, 64])
def test_paged_attention_decode(one_chip, page):
    from sparkdl_tpu.ops.pallas.paged_attention import (
        paged_attention_decode,
    )

    batch, n_pages = 8, 1024
    pool = _shape(one_chip, (n_pages, page, N_KV, HEAD_DIM), jnp.bfloat16)
    _compile(
        paged_attention_decode,
        _shape(one_chip, (batch, N_HEADS, HEAD_DIM), jnp.bfloat16),
        pool, pool,
        _shape(one_chip, (batch, 2048 // page), jnp.int32),
        _shape(one_chip, (batch,), jnp.int32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
@pytest.mark.parametrize("m", ROWS)
def test_int8_quant_matmul(one_chip, m, k, n):
    from sparkdl_tpu.ops.pallas.quantized_matmul import (
        quantized_matmul_pallas,
    )

    _compile(
        quantized_matmul_pallas,
        _shape(one_chip, (m, k), jnp.bfloat16),
        _shape(one_chip, (k, n), jnp.int8),
        _shape(one_chip, (n,), jnp.float32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
@pytest.mark.parametrize("m", ROWS)
def test_int4_quant_matmul(one_chip, m, k, n):
    from sparkdl_tpu.ops.pallas.quantized_matmul import (
        INT4_GROUP,
        quantized_matmul_int4_pallas,
    )

    _compile(
        quantized_matmul_int4_pallas,
        _shape(one_chip, (m, k), jnp.bfloat16),
        _shape(one_chip, (k // 2, n), jnp.int8),
        _shape(one_chip, (k // INT4_GROUP, n), jnp.float32))


@pytest.mark.parametrize("quant", ["", "int8", "int4"])
def test_paged_engine_decode_step(one_chip, monkeypatch, quant):
    """One decode step of the paged engine's own program at depth 1:
    the kernels as the model calls them, with everything around them.
    The program asks the backend whether to use its kernels, and here
    the backend is the CPU — the test answers for the described chip."""
    from sparkdl_tpu.models import Llama, LlamaConfig
    from sparkdl_tpu.models.serving import _engine_programs
    from sparkdl_tpu.ops import _dispatch

    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    n_slots, page, max_len = 8, 16, 2048
    max_pages = max_len // page
    cfg = LlamaConfig.llama3_8b(
        n_layers=1, dtype=jnp.bfloat16, decode=True, max_cache_len=max_len,
        page_size=page, n_pages=n_slots * max_pages + 1, quant=quant)
    model = Llama(cfg)
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    state = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), ints((n_slots, 1)),
        positions=ints((n_slots, 1)),
        block_tables=ints((n_slots, max_pages)))
    on_chip = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    decode_chunk = _engine_programs(cfg, 0.0)[4]
    compiled = decode_chunk.lower(
        on_chip(state["params"]), on_chip(state["cache"]),
        _shape(one_chip, (n_slots,), jnp.int32),
        _shape(one_chip, (n_slots,), jnp.int32),
        _shape(one_chip, (n_slots,), jnp.bool_),
        _shape(one_chip, (2,), jnp.uint32), 1,
        tables=_shape(one_chip, (n_slots, max_pages), jnp.int32),
    ).compile()
    # the paged kernel, and with quantized weights its seven
    # projections and the head beside it
    assert compiled.as_text().count("tpu_custom_call") >= (
        9 if quant else 1)


# Nemotron-3-Super's state-space layer at the benchmark cell's tokens
SSM_HEADS, SSM_HEAD_DIM, SSM_GROUPS, SSM_STATE, SSM_CHUNK = 128, 64, 8, 128, 128


@pytest.mark.parametrize("backward", [False, True])
def test_ssd_scan(one_chip, backward):
    """The scan's kernels alone at (1, 8192, 128 x 64, 8 x 128) with the
    blocks the rule chooses: the forward, and the forward that saves
    the states with the backward behind it."""
    from sparkdl_tpu.ops.ssd import ssd_chunked

    def fwd(*args):
        return ssd_chunked(*args, chunk=SSM_CHUNK, interpret=False)

    def fwd_bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=tuple(range(6)))(*args)

    seq = 8192
    heads = _shape(one_chip, (SSM_HEADS,), jnp.float32)
    shared = _shape(one_chip, (1, seq, SSM_GROUPS, SSM_STATE), jnp.bfloat16)
    compiled = _compile(
        fwd_bwd if backward else fwd,
        _shape(one_chip, (1, seq, SSM_HEADS, SSM_HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (1, seq, SSM_HEADS), jnp.float32), heads,
        shared, shared, heads)
    # (alone, the name stack starts at the kernel, and the forward's
    # instruction is named jvp_sparkdl_ssd_fwd_ under a gradient)
    kernels = [line.split(" = ")[0] for line in compiled.as_text().split("\n")
               if "tpu_custom_call" in line]
    assert len(kernels) == 1 + backward
    assert sum("sparkdl_ssd_fwd" in k for k in kernels) == 1
    assert sum("sparkdl_ssd_bwd" in k for k in kernels) == backward


@pytest.mark.parametrize("backward", [False, True])
def test_moe_rows(one_chip, backward):
    """The rows' way out and back alone at the hybrid cell's shape,
    8192 tokens x 22 picks of 1024: a take and an add, and behind them
    the gradients, which are the other kernel each (the way back's with
    its weights and its dots)."""
    from sparkdl_tpu.models.moe import _add, _take
    from sparkdl_tpu.ops.pallas.moe_rows import ROWS_TILE, takes_shape

    tokens, picks, width = 8192, 22, 1024
    assert takes_shape(tokens, width)
    static = (ROWS_TILE, False)

    def fwd(v, y, weights, w_row, here, order, n):
        tok = order // picks
        return (_take(v, tok, n, static),
                _add(y, weights, w_row, here, order, tok, n, static))

    def fwd_bwd(v, y, weights, *plan):
        def loss(*a):
            out = fwd(*a, *plan)
            return sum(x.astype(jnp.float32).sum() for x in out), out

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            v, y, weights)

    pairs = _shape(one_chip, (tokens * picks,), jnp.int32)
    compiled = _compile(
        fwd_bwd if backward else fwd,
        _shape(one_chip, (tokens, width), jnp.bfloat16),
        _shape(one_chip, (tokens * picks, width), jnp.bfloat16),
        _shape(one_chip, (tokens, picks), jnp.float32),
        _shape(one_chip, (tokens * picks,), jnp.float32),
        _shape(one_chip, (tokens, picks), jnp.bool_), pairs,
        _shape(one_chip, (), jnp.int32))
    kernels = [line.split(" = ")[0] for line in compiled.as_text().split("\n")
               if "tpu_custom_call" in line]
    assert len(kernels) == 2 * (1 + backward)
    assert sum("sparkdl_moe_take" in k for k in kernels) == 1 + backward
    assert sum("sparkdl_moe_add" in k for k in kernels) == 1 + backward


# (rows the kernel gets, groups held, d_model, width of the first
# product, d_ff): the expert cells at their published widths, and
# Mixtral's, whose down projection is the one contraction split
EXPERT_WIDTHS = {
    "glm": (32768, 64, 2048, 3072, 1536),
    "trinity": (65536, 128, 2048, 2048, 1024),
    "hybrid": (180224, 128, 1024, 2688, 2688),
    "mixtral": (4096, 8, 4096, 2 * 14336, 14336),
}


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("cell", list(EXPERT_WIDTHS))
def test_grouped_matmul(one_chip, monkeypatch, cell, backward):
    """Both grouped products of an expert layer, and their gradient in
    the rows (the transposed products), with the tiles the rule
    chooses: under Mosaic's default scope, which ``gmm`` does not
    widen, the whole contraction at every cell's width compiles. An
    overfull VMEM fails here, without a chip."""
    from sparkdl_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_pallas", lambda: True)
    rows, groups, d, up, ff = EXPERT_WIDTHS[cell]
    for k, n in ((d, up), (ff, d), (up, d), (d, ff)):
        tiles = gm.gmm_tiles(k, n, 2, 2)
        assert tiles.vmem_bytes <= gm.VMEM_BUDGET
        assert (tiles.tk == k) == ((cell, k) not in (
            ("mixtral", ff), ("mixtral", up))), (k, n, tiles)

    def fwd(x, w_in, w_down, sizes):
        hidden = jnp.square(gm.grouped_matmul(x, w_in, sizes)[:, :ff])
        return gm.grouped_matmul(hidden, w_down, sizes)

    def fwd_bwd(x, w_in, w_down, sizes):
        # squares on both sides keep the forward products in the program
        return jax.grad(lambda x: jnp.square(
            fwd(x, w_in, w_down, sizes).astype(jnp.float32)).sum())(x)

    compiled = _compile(
        fwd_bwd if backward else fwd,
        _shape(one_chip, (rows, d), jnp.bfloat16),
        _shape(one_chip, (groups, d, up), jnp.bfloat16),
        _shape(one_chip, (groups, ff, d), jnp.bfloat16),
        _shape(one_chip, (groups,), jnp.int32))
    stacks = re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"",
        compiled.as_text())
    assert len(stacks) == 2 * (1 + backward), stacks
    assert all(gm.NAME in s for s in stacks)


def test_train_step_carries_the_kernel_names(one_chip, monkeypatch):
    """One LoRA train step at depth 1 with remat: in the compiled
    program each flash kernel is an instruction named after its
    ``pallas_call(name=...)``, which is the name its events take in a
    device trace (``%sparkdl_flash_fwd.1 = ...``): forward (twice, the
    second under remat), dq and dk/dv are told apart by name alone."""
    import optax

    from sparkdl_tpu.models import Llama, LlamaConfig, lora_mask
    from sparkdl_tpu.ops import attention
    from sparkdl_tpu.parallel.train import make_lm_loss_fn, make_train_step

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    cfg = LlamaConfig.llama3_8b(
        n_layers=1, vocab_size=4096, dtype=jnp.bfloat16, attention="flash",
        remat=True, lora_rank=8, lora_targets=("q_proj", "v_proj"))
    model = Llama(cfg)
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    mask = lora_mask(params)
    opt = optax.masked(optax.adamw(1e-4), mask)
    step = make_train_step(
        make_lm_loss_fn(model, loss="fused", chunk=256, ce_bf16=True),
        opt, param_mask=mask)
    on_chip = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    tokens = _shape(one_chip, (1, 2048), jnp.int32)
    compiled = jax.jit(step).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
        {"inputs": tokens, "targets": tokens}).compile()
    kernels = re.findall(
        r"%(\w+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())
    assert sorted(kernels) == [
        "sparkdl_flash_dkv", "sparkdl_flash_dq", "sparkdl_flash_fwd",
        "sparkdl_flash_fwd"]


def test_hybrid_train_step_goes_with_the_rows_routed_here(
        one_chip, monkeypatch):
    """One LoRA step of the patterned decoder, a layer of each kind at
    Nemotron-3-Super's widths and the benchmark cell's 1 x 8192 tokens,
    the chip holding 128 of 512 routed experts: the scan's kernels and
    the sorted dispatch compile for the chip; the experts' products are
    ragged-dot kernels over the (token, pick) buffer, the rows go out
    and back by the take and add kernels and no gather makes the whole
    buffer, and nothing in the step has the shape of every expert held
    on every token, nor of every chunk's and head's decay."""
    import optax

    from sparkdl_tpu.models import HybridConfig, HybridDecoder, lora_mask, moe
    from sparkdl_tpu.ops import attention, grouped_matmul, ssd
    from sparkdl_tpu.parallel.train import make_lm_loss_fn, make_train_step

    for module in (attention, grouped_matmul, ssd, moe):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
    cfg = HybridConfig(
        pattern="ME*", vocab_size=4096, d_model=4096, n_heads=32,
        n_kv_heads=2, head_dim=128, ssm_heads=128, ssm_head_dim=64,
        ssm_groups=8, ssm_state=128, conv_kernel=4, chunk_size=128,
        n_routed_experts=512, experts_held=(128, 128), top_k=22,
        latent=1024, expert_d_ff=2688, shared_d_ff=5376, routed_scale=5.0,
        dtype=jnp.bfloat16, attention="flash", remat=True, lora_rank=8)
    model = HybridDecoder(cfg)
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    mask = lora_mask(params)
    opt = optax.masked(optax.adamw(1e-4), mask)
    step = make_train_step(
        make_lm_loss_fn(model, loss="fused", chunk=256, ce_bf16=True),
        opt, param_mask=mask)
    on_chip = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    tokens = _shape(one_chip, (1, 8192), jnp.int32)
    text = jax.jit(step).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
        {"inputs": tokens, "targets": tokens}).compile().as_text()
    stacks = re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"",
        text)
    # up and down, forward, the remat's forward again, and the backward
    # in the rows alone (the experts are frozen, so no product a group
    # for their own gradient is left), each under the experts' scope
    grouped = [s for s in stacks if grouped_matmul.NAME in s]
    assert len(grouped) == 6, stacks
    assert all("sparkdl.moe.experts" in s for s in grouped)
    # the rows' way out and back, each the other's gradient: out in the
    # forward, in the remat's forward again and (the way back's gradient)
    # in the backward; back in the forward and (the way out's gradient) in
    # the backward. The remat's forward needs no way back: nothing the
    # backward reads lies behind it. All under the scope that
    # `moe_dispatch_ms.train_hybrid` sums
    moved = [s for s in stacks if "sparkdl_moe_take" in s
             or "sparkdl_moe_add" in s]
    assert sorted(s.split("/")[-2] for s in moved) == (
        ["sparkdl_moe_add"] * 2 + ["sparkdl_moe_take"] * 3), moved
    assert all("sparkdl.moe.dispatch" in s for s in moved)
    assert sum("sparkdl.attn" in s for s in stacks) == 4
    # the scan: forward, the remat's forward again and the backward, each
    # under the scope that the benchmark's readers sum
    scan = [s for s in stacks if "sparkdl_ssd_" in s]
    assert sorted(s.split("/")[-2] for s in scan) == [
        "sparkdl_ssd_bwd", "sparkdl_ssd_fwd", "sparkdl_ssd_fwd"], stacks
    assert all("sparkdl.ssm.scan" in s for s in scan)
    # what is left under that scope beside the kernels: no loop over the
    # chunks, and nothing of a (chunk, chunk) decay a chunk and head
    for line in text.split("\n"):
        if "sparkdl.ssm.scan" in line:
            assert " while(" not in line, line
            assert not re.search(r"f32\[[\d,]*128,128\]", line), line
    rows = 8192 * 22
    assert f"bf16[{rows},2688]" in text
    for line in text.split("\n"):
        if "sparkdl.moe.dispatch" in line and " gather(" in line:
            assert f"[{rows},1024]" not in line.split(" gather(")[0], line
    for dense in ("[8192,128,2688]", "[128,8192,2688]", "[8192,2688,128]"):
        assert dense not in text
    for scope in ("sparkdl.ssm.scan", "sparkdl.ssm.conv", "sparkdl.moe.route",
                  "sparkdl.moe.dispatch", "sparkdl.moe.shared", "sparkdl.attn"):
        assert scope in text, scope


@pytest.mark.parametrize("backward", [False, True])
def test_flash_attention_at_head_size_256(one_chip, backward):
    """The three kernels at latent attention's expanded shape, 20 heads
    of 256 with as many key/value heads over S = 8192: tiles of 512,
    the streamed side whole under the rule's ceiling, and more VMEM
    asked of Mosaic than the reckoning."""
    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.ops.pallas.flash_attention import (
        VMEM_BUDGET,
        flash_tiles,
    )

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False, scale=256 ** -0.5)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for kernel in ("fwd", "dq", "dkv"):
        tiles = flash_tiles(kernel, 8192, 256, 2)
        assert tiles[:3] == (512, 512, 8192)
        assert 16 * 2 ** 20 < tiles.vmem_bytes <= VMEM_BUDGET
        assert tiles.vmem_limit > tiles.vmem_bytes
    qkv = _shape(one_chip, (1, 8192, 20, 256), jnp.bfloat16)
    compiled = _compile(fwd_bwd if backward else fwd, qkv, qkv, qkv)
    kernels = re.findall(
        r"%(\w+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())
    names = ["sparkdl_flash_fwd"] + ["sparkdl_flash_dq", "sparkdl_flash_dkv"
                                     ] * backward
    assert len(kernels) == len(names), kernels
    assert all(sum(name in k for k in kernels) == 1 for name in names), kernels


@pytest.mark.parametrize("backward", [False, True])
def test_flash_attention_with_a_window(one_chip, backward):
    """The three kernels with a causal window of 2048 over S = 8192 at
    32 heads of 128 (``trinitymini-lora-train``'s window layers): the
    rule's tiles, the streamed side whole (one grid step a tile, so the
    window's far edge and the diagonal are in one walk), the walk's
    three loops, inside the VMEM each kernel asks for."""
    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.ops.pallas.flash_attention import (
        VMEM_BUDGET,
        flash_tiles,
        tiles_walked,
    )

    def fwd(q, k, v):
        return flash_attention(q, k, v, window=2048, interpret=False)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for kernel in ("fwd", "dq", "dkv"):
        tiles = flash_tiles(kernel, 8192, HEAD_DIM, 2)
        assert tiles.major == 8192
        assert tiles.vmem_bytes <= VMEM_BUDGET and tiles.vmem_limit
        assert (tiles_walked(kernel, 8192, tiles, 2048)
                < 0.65 * tiles_walked(kernel, 8192, tiles))
    qkv = _shape(one_chip, (1, 8192, N_HEADS, HEAD_DIM), jnp.bfloat16)
    compiled = _compile(fwd_bwd if backward else fwd, qkv, qkv, qkv)
    kernels = re.findall(
        r"%(\w+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())
    names = ["sparkdl_flash_fwd"] + ["sparkdl_flash_dq", "sparkdl_flash_dkv"
                                     ] * backward
    assert len(kernels) == len(names), kernels
    assert all(sum(name in k for k in kernels) == 1 for name in names), kernels


def test_mla_train_step_fits_and_carries_its_kernels(one_chip, monkeypatch):
    """One LoRA step of ``glm47flash-lora-train`` as the benchmark
    builds it (``chipbench/configs/glm-4.7-flash.json``: pattern ``LD``
    + ``LG`` x 6 at the published widths, every expert held, 1 x 8192
    tokens): the flash kernels of each latent-attention mixer lie under
    ``sparkdl.mla.core``, the grouped products under
    ``sparkdl.moe.experts``, the rows move by the plain gathers (a token
    side of 8192 x 2048 float32 is past the rows' kernels), nothing has
    the shape of every expert on every token, and arguments and
    temporaries fit the chip."""
    import optax

    from chipbench import run as harness
    from chipbench.kinds.train_hybrid import init_params
    from sparkdl_tpu.models import HybridConfig, HybridDecoder, lora_mask, moe
    from sparkdl_tpu.ops import attention, grouped_matmul
    from sparkdl_tpu.parallel.train import make_lm_loss_fn, make_train_step

    for module in (attention, grouped_matmul, moe):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
    spec = harness.load_cell("glm47flash-lora-train")
    job = spec["traffic"]
    cfg = HybridConfig.from_published(
        spec["config"], dtype=jnp.bfloat16, lora_rank=job["lora_rank"],
        lora_targets=tuple(job["lora_targets"]), attention=job["attention"],
        remat=job["remat"])
    assert cfg.pattern == "LD" + "LG" * 6
    assert moe.dispatch_path(job["seq"], cfg.d_model) == "jnp"
    # the tree the kind builds: bf16 but for the float32 adapters
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    mask = lora_mask(params)
    opt = optax.masked(optax.adamw(job["lr"]), mask)
    step = make_train_step(
        make_lm_loss_fn(HybridDecoder(cfg), loss=job["loss"],
                        chunk=job["loss_chunk"], ce_bf16=True),
        opt, param_mask=mask)
    on_chip = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    tokens = _shape(one_chip, (job["batch"], job["seq"]), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
        {"inputs": tokens, "targets": tokens}).compile()
    memory = compiled.memory_analysis()
    assert 9.0e9 < memory.argument_size_in_bytes < 9.2e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15e9
    text = compiled.as_text()
    stacks = re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"",
        text)
    # seven mixers: forward, the remat's forward again, dq and dk/dv
    flash = [s for s in stacks if "sparkdl_flash_" in s]
    assert sorted(s.split("/")[-2] for s in flash) == (
        ["sparkdl_flash_dkv"] * 7 + ["sparkdl_flash_dq"] * 7
        + ["sparkdl_flash_fwd"] * 14), stacks
    assert all("sparkdl.mla/" in s and "sparkdl.mla.core" in s for s in flash)
    # six expert layers: gate | up and down, forward, the remat's
    # forward again and the backward in the rows alone
    grouped = [s for s in stacks if grouped_matmul.NAME in s]
    assert len(grouped) == 36 and len(stacks) == 64, stacks
    assert all("sparkdl.moe.experts" in s for s in grouped)
    assert len(stacks) >= job["min_kernels"]
    assert "sparkdl_moe_take" not in text and "sparkdl_moe_add" not in text
    rows = job["seq"] * cfg.top_k
    assert f"bf16[{rows},3072]" in text and f"bf16[{rows},2048]" in text
    for dense in ("[8192,64,1536]", "[64,8192,1536]", "[64,8192,3072]",
                  "[8192,64,3072]"):
        assert dense not in text
    for scope in ("sparkdl.mla.latent", "sparkdl.mla.core", "sparkdl.mlp",
                  "sparkdl.moe.route", "sparkdl.moe.dispatch",
                  "sparkdl.moe.shared", "sparkdl.lm_head_loss"):
        assert scope in text, scope
