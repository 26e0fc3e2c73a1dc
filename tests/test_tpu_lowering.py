"""Every Pallas entry point the TPU defaults route to lowers for a TPU.

Runs on the CPU in seconds: `jit(f).trace(...).lower(
lowering_platforms=("tpu",))` with `interpret=False` turns each kernel
into its Mosaic module and checks Pallas's own rules on the way (block
shapes against the (8, 128) tile, memory spaces, supported primitives,
and that a kernel under a multi-device `jit` sits inside a `shard_map`).
It does NOT run Mosaic's compiler: that lives in libtpu and runs at
`.compile()`. `TestMosaicCompiles` (slow) does call it, through the
compile-only TPU topology libtpu offers without a chip; what only the
chip can say (that the result is right and fits) is chip_smoke.py's.

The shapes are the flagship ones: the ResNet-50 batch-128 BN+ReLU tails
with a bf16 output and cotangent, and flash attention at [8, 8, T, 64]
bf16. The backward case fails on the commit before this file existed
(`(1, C)` partial-sum blocks over `[n_tiles, C]`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset.dataset import LocalDataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.nn import fusion
from bigdl_tpu.ops import bn_relu_kernel as bk
from bigdl_tpu.ops.attention_kernel import (flash_attention,
                                            flash_attention_forward)
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.mesh import build_mesh
from bigdl_tpu.parallel.sequence import make_sequence_parallel_attention
from bigdl_tpu.parallel.sharding import infer_param_specs

#: [N, C] views of the ResNet-50 BN+ReLU tails at batch 128
RESNET50_TAILS = [(1605632, 64), (401408, 64), (401408, 256), (6272, 2048)]
FLASH_LENGTHS = [100, 2000, 2048, 8192]


def lower_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def n_mosaic(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def struct(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture
def tpu_routing(monkeypatch):
    """Make the repo's `jax.default_backend() == "tpu"` routing checks
    take the TPU side while the process itself stays on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class TestBnReluLowers:
    @pytest.mark.parametrize("n,c", RESNET50_TAILS)
    def test_forward(self, n, c):
        lowered = lower_for_tpu(
            lambda x, s, b: bk.bn_relu_forward(
                x, s, b, True, out_dtype=jnp.bfloat16, interpret=False),
            struct((n, c), jnp.float32), struct((c,), jnp.float32),
            struct((c,), jnp.float32))
        assert n_mosaic(lowered) == 1

    @pytest.mark.parametrize("n,c", RESNET50_TAILS)
    def test_backward(self, n, c):
        lowered = lower_for_tpu(
            lambda x, s, b, g: bk.bn_relu_backward(
                x, s, b, g, True, interpret=False),
            struct((n, c), jnp.float32), struct((c,), jnp.float32),
            struct((c,), jnp.float32), struct((n, c), jnp.bfloat16))
        assert n_mosaic(lowered) == 1

    @pytest.mark.parametrize("n,c", RESNET50_TAILS)
    def test_tile_rows_fill_bf16_tiles(self, n, c):
        # a bf16 block's native tile is 16 rows, not the f32 8
        for itemsizes in ((4, 2), (4, 2, 4)):
            tile = bk._pick_tile_n(n, c, None, itemsizes)
            assert n % tile == 0 and tile % 16 == 0


class TestFlashLowers:
    @pytest.mark.parametrize("t", FLASH_LENGTHS)
    def test_forward_and_grad(self, t):
        q = struct((8, 8, t, 64), jnp.bfloat16)
        fwd = lower_for_tpu(
            lambda q, k, v: flash_attention(q, k, v, True, None, True),
            q, q, q)
        assert n_mosaic(fwd) == 1
        grad = lower_for_tpu(
            jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, True, None, True).astype(jnp.float32)),
                argnums=(0, 1, 2)), q, q, q)
        assert n_mosaic(grad) == 3  # forward, dq, dk/dv

    @pytest.mark.parametrize("t,window", [(128, None), (128, 4096),
                                          (12288, None), (12288, 4096)])
    def test_grouped_query_and_windowed_forward(self, t, window):
        """The serving prefill's kernel at the smallthinker-21b widths:
        28 query heads over 4 K/V heads of 128, a window of 4096."""
        q, kv = struct((2, 28, t, 128), jnp.bfloat16), \
            struct((2, 4, t, 128), jnp.bfloat16)
        fwd = lower_for_tpu(
            lambda q, k, v: flash_attention_forward(
                q, k, v, causal=True, interpret=False, window=window),
            q, kv, kv)
        assert n_mosaic(fwd) == 1
        name = "flash_fwd_gqa" if window is None else "flash_fwd_window"
        assert name in fwd.as_text(debug_info=True)

    @pytest.mark.parametrize("scheme,kernels", [("ring", 4), ("zigzag", 12)])
    def test_sequence_parallel_hops(self, tpu_routing, scheme, kernels):
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        q = struct((1, 8, 8192, 64), jnp.bfloat16,
                   NamedSharding(mesh, P(None, None, "seq", None)))
        fn = make_sequence_parallel_attention(mesh, scheme, "seq",
                                              causal=True)
        # ring: one hop kernel per device in the ring; zigzag: three
        # chunk-pair updates per hop
        assert n_mosaic(lower_for_tpu(fn, q, q, q)) == kernels


@pytest.fixture(scope="module")
def resnet50():
    from bigdl_tpu.models.resnet import ResNet50
    net = ResNet50(class_num=1000, s2d_stem=True)
    net.ensure_params(jax.random.PRNGKey(0))
    return net


def resnet50_step(net, data: int, model: int):
    """The DistriOptimizer train step of ResNet-50, batch 128 per data
    shard, bf16 compute, fusion at its default, with abstract arguments
    placed like `DistriOptimizer._place` places the real ones."""
    mesh = build_mesh(data=data, model=model,
                      devices=jax.devices()[:data * model])
    opt = DistriOptimizer(
        net, LocalDataSet([MiniBatch(np.zeros((1,)), np.zeros((1,)))]),
        nn.ClassNLLCriterion(), mesh=mesh)
    opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
    opt.set_compute_precision("bfloat16")

    def on(spec):
        return NamedSharding(mesh, spec)
    params = net.ensure_params()
    params = jax.tree_util.tree_map(
        lambda leaf, spec: struct(leaf.shape, leaf.dtype, on(spec)),
        params, infer_param_specs(params, mesh, opt.rules))
    slots = jax.eval_shape(opt.optim_method.init_state_with_masters, params)
    slots = jax.tree_util.tree_map(
        lambda leaf: struct(leaf.shape, leaf.dtype, on(P())), slots)
    state = jax.tree_util.tree_map(
        lambda leaf: struct(leaf.shape, leaf.dtype, on(P())), net._state)
    batch = 128 * data
    return opt._build_step(), (
        params, slots, state,
        struct((batch, 224, 224, 3), jnp.float32, on(P("data"))),
        struct((batch,), jnp.int32, on(P("data"))),
        struct((), jnp.float32, on(P())), struct((2,), jnp.uint32, on(P())))


class TestResNet50StepLowers:
    @pytest.mark.parametrize("data,model", [(1, 1), (4, 1)])
    def test_fused_train_step(self, tpu_routing, resnet50, data, model):
        # no ResNet-50 tail crosses into the Mosaic pair (PR 37: the
        # chip's pair-run, `bn_relu`'s docstring), on one chip as on
        # one of four; what the matcher collapses lowers to the unfused
        # step's jaxpr, equation for equation
        assert fusion.fusion_enabled()
        step, args = resnet50_step(resnet50, data, model)
        traced = step.trace(*args)
        assert bk.count_fused_calls(traced.jaxpr) == 0
        lowered = traced.lower(lowering_platforms=("tpu",))
        assert n_mosaic(lowered) == 0
        with fusion.fusion_scope(False):
            step, args = resnet50_step(resnet50, data, model)
            unfused = step.trace(*args)
        assert str(traced.jaxpr) == str(unfused.jaxpr)


#: ResNet-50's 33 BN+ReLU tails at 128 images fall into five classes of
#: [rows, channels]: the stem; the other 64-channel tails; 128; 256; 512
RESNET50_TAIL_CLASSES = [
    [(1605632, 64)], [(401408, 64)], [(401408, 128), (100352, 128)],
    [(100352, 256), (25088, 256)], [(25088, 512), (6272, 512)]]


class TestBnReluRouter:
    @pytest.mark.parametrize("shapes", RESNET50_TAIL_CLASSES,
                             ids=["stem", "c64", "c128", "c256", "c512"])
    @pytest.mark.parametrize("fuse_env", [None, "0", "1"])
    def test_decision_follows_shape_and_dtypes_alone(
            self, tpu_routing, monkeypatch, shapes, fuse_env):
        # the chip's table is empty (no class keeps the kernel), whatever
        # the environment says and on a second call as on the first
        if fuse_env is None:
            monkeypatch.delenv("BIGDL_TPU_FUSE_BN_RELU", raising=False)
        else:
            monkeypatch.setenv("BIGDL_TPU_FUSE_BN_RELU", fuse_env)
        for n, c in shapes:
            args = (struct((n, c), jnp.float32), struct((c,), jnp.float32),
                    struct((c,), jnp.float32))
            for out_dtype in (jnp.bfloat16, jnp.float32):
                def tail(x, s, b):
                    return bk.bn_relu(x, s, b, True, out_dtype)
                for _ in range(2):
                    jaxpr = jax.make_jaxpr(tail)(*args)
                    assert bk.count_fused_calls(jaxpr) == 0
                assert n_mosaic(lower_for_tpu(tail, *args)) == 0


# ---------------------------------------------------------------------- #
# Mosaic's own compile, without a chip
# ---------------------------------------------------------------------- #

def _v5e_device():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no compile-only TPU topology: {e!r}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow
class TestMosaicCompiles:
    """`lowered.compile()` against libtpu's compile-only v5e topology runs
    the real Mosaic compiler on the CPU host: it catches what Pallas's
    lowering cannot (unprovable slice alignment, VMEM overflow,
    unsupported layouts)."""

    @pytest.mark.parametrize("n,c", RESNET50_TAILS)
    def test_bn_relu_pair(self, n, c):
        on = _v5e_device()
        lower_for_tpu(
            lambda x, s, b: bk.bn_relu_forward(
                x, s, b, True, out_dtype=jnp.bfloat16, interpret=False),
            struct((n, c), jnp.float32, on), struct((c,), jnp.float32, on),
            struct((c,), jnp.float32, on)).compile()
        lower_for_tpu(
            lambda x, s, b, g: bk.bn_relu_backward(
                x, s, b, g, True, interpret=False),
            struct((n, c), jnp.float32, on), struct((c,), jnp.float32, on),
            struct((c,), jnp.float32, on),
            struct((n, c), jnp.bfloat16, on)).compile()

    @pytest.mark.parametrize("t,window", [(128, 4096), (16384, None),
                                          (16384, 4096)])
    def test_grouped_query_and_windowed_forward(self, t, window):
        # K/V blocks come through the grid: a whole head of 16 384
        # positions (4 MB, twice, double-buffered) would not fit VMEM
        on = _v5e_device()
        with jax.default_matmul_precision("bfloat16"):
            lower_for_tpu(
                lambda q, k, v: flash_attention_forward(
                    q, k, v, causal=True, interpret=False, window=window),
                struct((2, 28, t, 128), jnp.bfloat16, on),
                struct((2, 4, t, 128), jnp.bfloat16, on),
                struct((2, 4, t, 128), jnp.bfloat16, on)).compile()

    @pytest.mark.parametrize("t", FLASH_LENGTHS)
    def test_flash_forward_and_grad(self, t):
        # t=100 failed here before block_q was rounded up to 128 lanes:
        # "cannot statically prove that index in dimension 2 is a
        # multiple of 128" in the dk/dv kernel's lse slice
        q = struct((8, 8, t, 64), jnp.bfloat16, _v5e_device())
        # the precision the bf16 train step runs under (conftest's
        # "highest" makes the in-kernel dots multi-pass, and the t=8192
        # dk/dv kernel then needs 18.1 of the 16 MiB of scoped VMEM)
        with jax.default_matmul_precision("bfloat16"):
            lower_for_tpu(
                jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, True, None, True).astype(jnp.float32)),
                    argnums=(0, 1, 2)), q, q, q).compile()


    def test_latent_decode_at_the_cells_shapes(self):
        # 32 slots x 16384 of a 512-wide latent and 64 rotary keys, 32
        # heads, blocks of 512: two blocks of each in VMEM (at the
        # serving cells' precision: under "highest" Mosaic refuses the
        # bf16 operands of a multi-pass dot, "Bad lhs type")
        from bigdl_tpu.ops.latent_decode_kernel import mla_decode
        on = _v5e_device()
        with jax.default_matmul_precision("bfloat16"):
            lower_for_tpu(
                lambda q, qpe, c, pe, pos: mla_decode(
                    q, qpe, c, pe, pos, 192 ** -0.5, 512, interpret=False),
                struct((32, 32, 512), jnp.bfloat16, on),
                struct((32, 32, 64), jnp.bfloat16, on),
                struct((32, 16384, 512), jnp.bfloat16, on),
                struct((32, 16384, 64), jnp.bfloat16, on),
                struct((32,), jnp.int32, on)).compile()

    @pytest.mark.parametrize("heads,kv_heads,depth", [
        (28, 4, 16384), (28, 4, 4096), (30, 30, 2048)],
        ids=["sparse-full", "sparse-window", "hybrid-full"])
    def test_grouped_decode_at_the_cells_shapes(self, heads, kv_heads,
                                                depth, monkeypatch):
        # 32 slots, heads of 128, bf16, in the block `block_for` gives
        # the shapes on a TPU
        from bigdl_tpu.ops import gqa_decode_kernel as gdk
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        block = gdk.block_for(depth, kv_heads, 128, 2)
        on = _v5e_device()
        kv = struct((32, kv_heads, depth, 128), jnp.bfloat16, on)
        with jax.default_matmul_precision("bfloat16"):
            lower_for_tpu(
                lambda q, k, v, pos: gdk.gqa_decode(q, k, v, pos, block,
                                                    interpret=False),
                struct((32, heads, 1, 128), jnp.bfloat16, on), kv, kv,
                struct((32,), jnp.int32, on)).compile()


# ---------------------------------------------------------------------- #
# the serving engine's decode program at the serving cell's shapes
# ---------------------------------------------------------------------- #

def _assert_one_call_a_layer_and_no_cache_moved(compiled, kernel, n_layer,
                                                buffers, donated):
    """The compiled decode program holds `n_layer` Mosaic calls, all
    named `kernel`; no cache buffer of the shapes `buffers` is copied or
    relaid out; the donated cache (at least `donated` bytes) is updated
    in place with little beside it."""
    import re
    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    calls = re.findall(rf"^\s*%{kernel}[\w.]* = \S+ custom-call\(",
                       entry, re.M)
    assert len(calls) == n_layer == hlo.count("tpu_custom_call")
    moved = [line for line in entry.splitlines()
             if re.search(r" (copy|copy-start|transpose)\(", line)
             and line.split(" = ", 1)[-1].lstrip().startswith(buffers)]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= donated
    assert m.temp_size_in_bytes < 100_000_000


def _compile_decode_program(model, params, cache, slots, on):
    """`GenerationEngine`'s own `jit__decode_fn` for `model`, compiled by
    libtpu for the described device `on` from shapes alone, at the
    precision the serving cells run under."""
    from bigdl_tpu.serving import GenerationEngine
    # the engine only keeps the parameters; its own cache stays tiny
    model.set_params(params)
    eng = GenerationEngine(model, slots=1, max_len=2, start=False)
    try:
        ids = struct((slots,), jnp.int32, on)
        with jax.default_matmul_precision("bfloat16"):
            return eng._decode._jit.trace(
                params, cache, ids, ids, struct((slots,), jnp.bool_, on),
                ids).lower(lowering_platforms=("tpu",)).compile()
    finally:
        eng.close(drain=False)


class TestDecodeProgramCompiles:
    """`GenerationEngine`'s own `jit__decode_fn` over `TransformerLM` at
    the shapes of `neox-3.6b.serve-chat` (32 slots x 2048, 22 heads of
    128, 4 layers, bf16 weights and cache), compiled by libtpu for a v5e
    that is not attached. Nothing runs, so nothing here is a timing."""

    def test_one_four_branch_conditional_a_layer_and_no_kernel(self):
        import re
        from bigdl_tpu.models.transformer import TransformerLM
        from bigdl_tpu.nn import kv_cache
        on = _v5e_device()
        slots, max_len, n_layer = 32, 2048, 4
        model = TransformerLM(32000, embed_dim=2816, n_layer=n_layer,
                              n_head=22, mlp_ratio=4, max_len=max_len)

        def on_chip(tree, dtype=None):
            return jax.tree_util.tree_map(
                lambda a: struct(a.shape, dtype or a.dtype, on), tree)
        params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                         jnp.bfloat16)
        cache = on_chip(jax.eval_shape(
            lambda: model.init_cache(slots, max_len, jnp.bfloat16)))
        compiled = _compile_decode_program(model, params, cache, slots, on)
        hlo = compiled.as_text()
        assert "jit__decode_fn" in hlo
        conditionals = re.findall(
            r" conditional\(.*branch_computations=\{([^}]*)\}", hlo)
        assert len(conditionals) == n_layer
        assert all(len(c.split(",")) == 4 for c in conditionals)
        # the branch index is ONE value for the four layers, computed
        # from `positions` against the thresholds the host counts by
        # (`kv_cache.rung_index` over `depth_rungs`: the engine's
        # `decode_steps_by_depth`): walk its operands back
        index = set(re.findall(r" conditional\((%[\w.\-]+),", hlo))
        assert len(index) == 1
        defs = dict(re.findall(r"^\s*(%[\w.\-]+) = (.*)$", hlo, re.M))
        reached, todo = set(), list(index)
        while todo:
            name = todo.pop()
            if name in defs and name not in reached:
                reached.add(name)
                todo += re.findall(r"%[\w.\-]+",
                                   defs[name].split(", metadata=")[0])
        sources = [defs[n] for n in reached]
        below = ", ".join(map(str, kv_cache.depth_rungs(max_len)[:-1]))
        assert any(f"constant({{{below}}})" in d for d in sources)
        inputs = [d for d in sources if " parameter(" in d]
        assert len(inputs) == 1 and 'op_name="positions"' in inputs[0]
        # a Mosaic kernel over the [32, 22, 2048, 128] cache would be
        # handed to counts/flash_attention.py by `flash_roofline.serve`
        # (benchmarks/trace/reduce.py: a custom-call with
        # kernel_metadata) and read thousands of percent
        assert "kernel_metadata" not in hlo
        assert "tpu_custom_call" not in hlo
        # the donated cache is updated in place and only read by the
        # branches: no second buffer of its size is planned (one layer's
        # K is 369 MB)
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= 2 * n_layer * 369_000_000
        assert m.temp_size_in_bytes < 100_000_000


class TestSparseDecodeProgramCompiles:
    """`GenerationEngine`'s `jit__decode_fn` over `SparseDecoderLM` at
    the shapes of `smallthinker-21b.serve-mixed` (32 slots x 16384, 28
    query over 4 K/V heads of 128, 64 experts of 768 with 6 active, 8
    layers [full, window x3] x 2, bf16 weights and cache, float32 norms
    and router), compiled by libtpu for a v5e that is not attached: the
    decode step takes `RoutedExperts._few_rows`, whose three products a
    layer must read `wg`, `wu` and `wd` where they lie. Nothing runs, so
    nothing here is a timing."""

    @staticmethod
    def _program(on):
        from bigdl_tpu.models.decoder import LayerSpec, SparseDecoderLM
        slots, max_len = 32, 16384
        layers = [LayerSpec(window=4096 if i % 4 else None,
                            rope_base=1.5e6 if i % 4 else None)
                  for i in range(8)]
        model = SparseDecoderLM(151936, embed_dim=2560, n_head=28,
                                n_kv_head=4, head_dim=128, layers=layers,
                                n_experts=64, expert_dim=768, top_k=6,
                                max_len=max_len, cache_dtype=jnp.bfloat16)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: struct(
                a.shape, jnp.float32 if {"router", "ln1", "ln2", "norm"}
                & {getattr(k, "key", None) for k in path} else jnp.bfloat16,
                on),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        cache = jax.tree_util.tree_map(
            lambda a: struct(a.shape, a.dtype, on),
            jax.eval_shape(lambda: model.init_cache(slots, max_len)))
        return _compile_decode_program(model, params, cache, slots, on)

    def test_experts_are_read_in_place_and_named(self):
        import re
        on = _v5e_device()
        n_layer = 8
        compiled = self._program(on)
        hlo = compiled.as_text()
        entry = hlo[hlo.index("ENTRY"):]
        assert "jit__decode_fn" in hlo and "ragged-dot" not in hlo
        # no second copy and no relayout of a layer's experts: 252 MB is
        # 0.31 ms of the memory's time, and the chip has no room for it
        # (the cell peaks at 14.34 of 16 GB)
        expert_shapes = ("bf16[64,768,2560]", "bf16[64,2560,768]")
        moved = [line for line in entry.splitlines()
                 if re.search(r" (copy|copy-start|transpose)\(", line)
                 and line.split(" = ", 1)[-1].lstrip().startswith(
                     expert_shapes)]
        assert not moved, moved
        m = compiled.memory_analysis()
        assert m.temp_size_in_bytes < 100_000_000
        assert m.alias_size_in_bytes >= 3_700_000_000    # the donated cache
        # each layer's three expert weights are operands of the step's
        # own fusions, once each, and a device trace can tell the down
        # projection from the gate and up products by their scopes
        for leaf in ("wg", "wu", "wd"):
            for i in range(n_layer):
                readers = re.findall(
                    rf"^\s*%[\w.\-]+ = .*\(.*%params__block{i}____experts"
                    rf"____{leaf}__[\w.]*[,)]", entry, re.M)
                assert len(readers) == 1, (leaf, i, readers)
        assert entry.count("moe experts/moe down/") >= n_layer
        assert entry.count("moe experts/moe gate up/") >= n_layer
        assert "tpu_custom_call" not in hlo

    def test_one_gqa_decode_a_layer_and_no_copy_of_a_cache(self,
                                                           tpu_routing):
        """Under the TPU's routing each of the eight layers reads its
        cache through ONE `gqa_decode` call, the two full layers' 16384
        deep and the six rings of 4096, and no K/V buffer is copied or
        relaid out around the calls."""
        compiled = self._program(_v5e_device())
        _assert_one_call_a_layer_and_no_cache_moved(
            compiled, "gqa_decode", 8,
            ("bf16[32,4,16384,128]", "bf16[32,4,4096,128]"), 3_700_000_000)


class TestHybridDecodeProgramCompiles:
    """`GenerationEngine`'s `jit__decode_fn` over `DecoderLM` at the
    shapes of `olmo-hybrid-7b.serve-decode` (32 slots x 2048, hidden
    3840, [gated delta rule x3, full attention] x 4 with 30 heads of
    key 96 / value 192 and 30 of 128, a dense FFN of 11008, vocabulary
    100352, bf16 weights, K/V and tails, float32 state and norms),
    compiled by libtpu for a v5e that is not attached. Nothing runs, so
    nothing here is a timing."""

    @staticmethod
    def _program(on):
        from bigdl_tpu.models.decoder import DecoderLM, LayerSpec
        slots, max_len, n_layer = 32, 2048, 16
        layers = [LayerSpec(mixer="attention" if i % 4 == 3
                            else "gated_delta", ffn="dense", norm="output")
                  for i in range(n_layer)]
        model = DecoderLM(100352, embed_dim=3840, n_head=30, n_kv_head=30,
                          head_dim=128, layers=layers, max_len=max_len,
                          cache_dtype=jnp.bfloat16, ffn_dim=11008,
                          qk_norm=True, linear_heads=30, linear_key_dim=96,
                          linear_value_dim=192)
        f32 = {"ln1", "ln2", "norm", "q_norm", "k_norm", "a_log", "dt_bias"}
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: struct(
                a.shape, jnp.float32 if f32
                & {getattr(k, "key", None) for k in path} else jnp.bfloat16,
                on),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        cache = jax.tree_util.tree_map(
            lambda a: struct(a.shape, a.dtype, on),
            jax.eval_shape(lambda: model.init_cache(slots, max_len)))
        assert sum(1 for s in cache["state"] if s is not None) == 12
        return _compile_decode_program(model, params, cache, slots, on)

    def test_the_state_is_replaced_in_place_by_one_fusion_a_layer(self):
        import re
        state = "f32[32,30,96,192]"
        compiled = self._program(_v5e_device())
        hlo = compiled.as_text()
        entry = hlo[hlo.index("ENTRY"):]
        assert "jit__decode_fn" in hlo and "tpu_custom_call" not in hlo
        # every slot's state is updated where it lies: 12 x 70.8 MB of
        # states, 4 x 2 x 503 MB of K/V and the tails are donated and
        # aliased, and nothing of a state's size is planned beside them
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= 4_900_000_000
        assert m.temp_size_in_bytes < 150_000_000
        moved = [line for line in entry.splitlines()
                 if re.search(r" (copy|copy-start|transpose)\(", line)
                 and line.split(" = ", 1)[-1].lstrip().startswith(state)]
        assert not moved, moved
        # XLA's form of the update (PERF.md, PR 36): a fusion that reads
        # a layer's state for S^T k and S^T q, and one that reads it
        # again and writes the new one with the largest |S| reduced in
        # the same pass: two reads and a write where a kernel would make
        # one of each. Each state is written by ONE instruction
        writers = re.findall(
            rf"^\s*%[\w.\-]+ = \(f32\[32\]\S*, {re.escape(state)}\S*\) "
            r"fusion\(", entry, re.M)
        assert len(writers) == 12, len(writers)
        assert entry.count("linear attention/gdn step/") >= 12
        assert entry.count("linear attention/gdn conv/") >= 12
        assert "full attention/" in entry and "dense ffn/" in entry

    def test_one_gqa_decode_a_layer_and_no_copy_of_a_cache(self,
                                                           tpu_routing):
        """Under the TPU's routing each of the four full layers reads its
        cache through ONE `gqa_decode` call (30 K/V heads of 128, 2048
        deep), and no K/V buffer is copied or relaid out around them."""
        compiled = self._program(_v5e_device())
        _assert_one_call_a_layer_and_no_cache_moved(
            compiled, "gqa_decode", 4, ("bf16[32,30,2048,128]",),
            4_900_000_000)


# ---------------------------------------------------------------------- #
# latent attention's decode step: one `mla_decode` kernel a layer
# ---------------------------------------------------------------------- #

def _tiny_decode_step(model, slots, max_len):
    """`model.apply_step` lowered for a TPU from shapes alone."""
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    ids = struct((slots,), jnp.int32)
    return lower_for_tpu(model.apply_step, params, ids, cache, ids)


class TestLatentDecodeLowers:
    """The tiny decoders of the three kinds that take `DecoderLM`'s decode
    step (tiny-latent's widths for the latent one) under the TPU's
    routing: a latent layer's step is ONE Mosaic call, `mla_decode`; a
    grouped-query layer's is ONE `gqa_decode` where its cache is a whole
    number of blocks deep, and plain XLA where it is not."""

    def test_one_kernel_a_latent_layer(self, tpu_routing):
        from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind,
                                              LatentDims, LayerSpec)
        layers = [LayerSpec(mixer="latent", rope_base=1e6, ffn="dense")] \
            + [LayerSpec(mixer="latent", rope_base=1e6, shared=64,
                         router_reads="ffn")] * 5
        model = DecoderLM(128, 64, 4, 4, 24, layers, n_experts=16,
                          expert_dim=32, top_k=3, ffn_dim=128,
                          latent=LatentDims(16, 8, 16, 32),
                          experts=ExpertsKind("silu", "sigmoid", 2.448))
        lowered = _tiny_decode_step(model, 4, 256)
        assert n_mosaic(lowered) == 6
        assert lowered.as_text(debug_info=True).count('"mla_decode"') >= 1
        # a cache the blocks do not divide keeps the plain-XLA form
        assert n_mosaic(_tiny_decode_step(model, 4, 200)) == 0

    @pytest.mark.parametrize("kind", ["sparse", "hybrid"])
    def test_no_kernel_in_the_other_decode_steps(self, tpu_routing, kind):
        from bigdl_tpu.models.decoder import DecoderLM, LayerSpec
        if kind == "sparse":
            layers = [LayerSpec(window=64 if i % 4 else None,
                                rope_base=1e6 if i % 4 else None)
                      for i in range(4)]
            model = DecoderLM(128, 64, 4, 2, 16, layers, n_experts=8,
                              expert_dim=32, top_k=2)
        else:
            layers = [LayerSpec(mixer="attention" if i % 4 == 3
                                else "gated_delta", ffn="dense",
                                norm="output") for i in range(4)]
            model = DecoderLM(128, 64, 4, 4, 16, layers, ffn_dim=128,
                              qk_norm=True, linear_heads=4,
                              linear_key_dim=8, linear_value_dim=16)
        # the one full layer's 256 positions are two blocks; the sparse
        # model's rings of 64 are no whole block and stay plain XLA, as
        # does every layer of a cache 200 deep
        lowered = _tiny_decode_step(model, 4, 256)
        assert n_mosaic(lowered) == 1
        assert lowered.as_text(debug_info=True).count('"gqa_decode"') >= 1
        assert n_mosaic(_tiny_decode_step(model, 4, 200)) == 0


class TestLatentDecodeProgramCompiles:
    """`GenerationEngine`'s `jit__decode_fn` over `DecoderLM` at the
    shapes of `kanana-2-30b-a3b.serve-docs` (32 slots x 16384, hidden
    2048, six latent layers of 32 heads over a 512-wide latent and a
    64-wide rotary key, 128 experts of 768 with 6 active beside a shared
    one, vocabulary 128256, bf16 weights and latent cache, float32
    norms and router), compiled by libtpu for a v5e that is not attached
    under the TPU's routing. Nothing runs, so nothing here is a
    timing."""

    def test_one_mla_decode_a_layer_and_no_copy_of_a_cache(self,
                                                           tpu_routing):
        import re
        from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind,
                                              LatentDims, LayerSpec)
        on = _v5e_device()
        slots, max_len, n_layer = 32, 16384, 6
        layers = [LayerSpec(mixer="latent", rope_base=1e6, ffn="dense")] \
            + [LayerSpec(mixer="latent", rope_base=1e6, shared=1536,
                         router_reads="ffn")] * (n_layer - 1)
        model = DecoderLM(128256, 2048, 32, 32, 192, layers,
                          n_experts=128, expert_dim=768, top_k=6,
                          cache_dtype=jnp.bfloat16, ffn_dim=6144,
                          latent=LatentDims(128, 64, 128, 512),
                          experts=ExpertsKind("silu", "sigmoid", 2.448))
        f32 = {"router", "router_bias", "ln1", "ln2", "norm", "kv_norm"}
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: struct(
                a.shape, jnp.float32 if f32
                & {getattr(k, "key", None) for k in path} else jnp.bfloat16,
                on),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        cache = jax.tree_util.tree_map(
            lambda a: struct(a.shape, a.dtype, on),
            jax.eval_shape(lambda: model.init_cache(slots, max_len)))
        compiled = _compile_decode_program(model, params, cache, slots, on)
        hlo = compiled.as_text()
        entry = hlo[hlo.index("ENTRY"):]
        calls = re.findall(r"^\s*%mla_decode[\w.]* = \S+ custom-call\(",
                           entry, re.M)
        assert len(calls) == n_layer == hlo.count("tpu_custom_call")
        # the absorbed XLA form's float32 scores [slots, heads, depth]
        # are gone, and no whole latent or rotary-key buffer is copied
        # or relaid out around the calls: the keys stay positions-minor,
        # which the kernel's [slots, rope, depth] view takes as a bitcast
        assert "f32[32,32,16384]" not in entry
        moved = [line for line in entry.splitlines()
                 if re.search(r" (copy|copy-start|transpose)\(", line)
                 and re.match(r"bf16\[32,(16384|64),(512|64|16384)\]",
                              line.split(" = ", 1)[-1].lstrip())]
        assert not moved, moved
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= 3_600_000_000     # the donated cache
        assert m.temp_size_in_bytes < 100_000_000
