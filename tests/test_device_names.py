"""The names the device trace and ``compiled.as_text()`` show: every
Pallas kernel's ``name=`` constant is its HLO instruction name
(``%veles_conv_wgrad``, the key of an ``XLA Ops`` event), and the fused
step's ``jax.named_scope``s (``l<k>_<layer type>``, ``loss``,
``update``, the loader's ``loader_gather``) ride in ``op_name``.

Compiled here for a DESCRIBED v5e (no chip attached; the
on-chip-measurement guide, section 2): the topology is described inside
a module-scoped fixture, never at import, and every such compile lives
in this one file.  The scopes change metadata only: the last test
proves loss and gradients bit-identical with and without them."""

import contextlib
import re

import numpy
import pytest

from veles_tpu import compiler
from veles_tpu.models import zoo
from veles_tpu.ops import common, conv_vjp, gather, pool_bwd

#: conv + pool + dense, so both backward kernels are in the step
TOY_CNN = [
    {"type": "conv_str", "n_kernels": 8, "kx": 3, "ky": 3, "padding": 1,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
    {"type": "all2all_tanh", "output_sample_shape": 16,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "softmax", "output_sample_shape": 4,
     "learning_rate": 0.01, "gradient_moment": 0.9},
]
TOY_INPUT = (14, 14, 3)
TOY_SCOPES = ("l0_ConvStrictRELU", "l1_MaxPooling", "l2_All2AllTanh",
              "l3_All2AllSoftmax")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % exc)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels as the chip gets them: Mosaic (no interpreter), the
    hand-scheduled backward on, and a silent compile cache (a deviceless
    compile can be written to the persistent cache but not read back)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(common, "interpret_mode", lambda: False)
    monkeypatch.setattr(common, "PALLAS_BWD_ENV", "1")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(fn, sharding, *avals):
    """``compiled.as_text()`` of ``fn`` for the described chip."""
    import jax

    def place(aval):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=sharding)

    avals = jax.tree.map(place, avals)
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()


def mosaic_calls(text):
    """{instruction name: op_name} of the text's Mosaic custom calls."""
    found = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split(" = ", 1)[0].split()[-1]
        op_name = re.search(r'op_name="([^"]*)"', line)
        found[name] = op_name.group(1) if op_name else ""
    return found


def aval(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, numpy.dtype(dtype))


def test_conv_wgrad_is_named_at_alexnet_conv2(topo, one_chip, mosaic):
    """AlexNet conv2 at batch 256, bfloat16: 5x5 over 27x27x96 -> 256,
    the step's second-largest cost (PERF.md section 5)."""
    import jax.numpy as jnp
    x = aval((256, 27, 27, 96), jnp.bfloat16)
    y = aval((256, 27, 27, 256), jnp.bfloat16)

    def wgrad(x, y, dy):
        return conv_vjp._fused_wgrad_jit(
            x, y, dy, "strict_relu", 5, 5, (27, 27), (2, 2, 2, 2),
            (1, 1), 0, None, False)

    calls = mosaic_calls(compiled_text(wgrad, one_chip, x, y, y))
    assert len(calls) == 1, calls
    (name, op_name), = calls.items()
    assert re.match(r"^%veles_conv_wgrad(\.\d+)?$", name), name
    assert conv_vjp.KERNEL_NAME in op_name


def test_pool_bwd_is_named_at_alexnet_pool1(topo, one_chip, mosaic):
    """AlexNet's first pooling at batch 256: 3x3 stride 2 over
    55x55x96, whose backward feeds conv2's."""
    import jax.numpy as jnp
    x = aval((256, 55, 55, 96), jnp.bfloat16)
    y = aval((256, 27, 27, 96), jnp.bfloat16)

    def bwd(x, y, dy):
        return pool_bwd._max_pool_bwd_jit(x, y, dy, (3, 3), (2, 2),
                                          False)

    calls = mosaic_calls(compiled_text(bwd, one_chip, x, y, y))
    assert len(calls) == 1, calls
    (name, op_name), = calls.items()
    assert re.match(r"^%veles_pool_bwd(\.\d+)?$", name), name
    assert pool_bwd.KERNEL_NAME in op_name


def test_causal_latent_attention_kernels_at_the_decoders_widths(
        topo, one_chip, mosaic):
    """The decoder cell's attention: 8,192 tokens, keys 192 wide against
    values 128 wide, causal, bfloat16 products, at the tiles
    ``blocks=None`` takes; forward and both backward kernels pass
    Mosaic and keep their names through ``custom_vjp``."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import attention
    q = aval((4, 8192, 192), jnp.bfloat16)
    v = aval((4, 8192, 128), jnp.bfloat16)

    def loss_grads(q, k, v):
        return jax.grad(lambda *a: attention.flash_attention(
            *a, causal=True, product_dtype=jnp.bfloat16).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    calls = mosaic_calls(compiled_text(loss_grads, one_chip, q, q, v))
    kernels = sorted(name.lstrip("%").split(".")[0] for name in calls)
    assert kernels == sorted((attention.FWD_KERNEL_NAME,
                              attention.DQ_KERNEL_NAME,
                              attention.DKV_KERNEL_NAME)), calls


@pytest.mark.parametrize("window, names", [
    (2048, ("WIN_FWD_KERNEL_NAME", "WIN_DQ_KERNEL_NAME",
            "WIN_DKV_KERNEL_NAME")),
    (None, ("FWD_KERNEL_NAME", "DQ_KERNEL_NAME", "DKV_KERNEL_NAME"))])
def test_grouped_window_attention_kernels_at_the_second_decoders_widths(
        topo, one_chip, mosaic, window, names):
    """The ``trinity_mini`` configuration's attention: 8,192 tokens, 32
    query heads on 4 KV heads 128 wide, bfloat16 products, under the
    2,048-token window (band-only grids, the group an inner axis of the
    dk/dv grid) and without it: the three kernels pass Mosaic under
    their own names, and K and V reach them with 4 rows — never repeated
    to the 32 query heads."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import attention
    q = aval((32, 8192, 128), jnp.bfloat16)
    k = aval((4, 8192, 128), jnp.bfloat16)

    def loss_grads(q, k, v):
        return jax.grad(lambda *a: attention.flash_attention(
            *a, causal=True, window=window,
            product_dtype=jnp.bfloat16).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(loss_grads, one_chip, q, k, k)
    kernels = sorted(name.lstrip("%").split(".")[0]
                     for name in mosaic_calls(text))
    assert kernels == sorted(getattr(attention, name) for name in names)
    layouts = [line.split("operand_layout_constraints=")[1].split(
        "frontend_attributes")[0] for line in text.splitlines()
        if "operand_layout_constraints=" in line and "veles_flash" in line]
    assert len(layouts) == 3, text[:2000]
    for layout in layouts:
        shapes = re.findall(r"bf16\[([0-9,]*)\]", layout)
        assert shapes[:3] == ["32,8192,128", "4,8192,128", "4,8192,128"]


def toy_step(batch=8):
    import jax
    plans, state, _ = zoo.build_plans_and_state(TOY_CNN, TOY_INPUT,
                                                seed=3)
    step = compiler._build_step_fn(plans, "softmax")
    shapes = jax.tree.map(lambda leaf: aval(leaf.shape, leaf.dtype),
                          state)
    return plans, state, step, (
        shapes, aval((batch,) + TOY_INPUT, numpy.float32),
        aval((batch,), numpy.int32), aval((), numpy.float32))


def test_fused_step_names_kernels_layers_and_phases(topo, one_chip,
                                                    mosaic):
    """Through jit, value_and_grad (jvp + transpose) and custom_vjp:
    the kernels keep their names, and each sits under its layer's
    scope."""
    _, _, step, avals = toy_step()
    text = compiled_text(step, one_chip, *avals)
    calls = mosaic_calls(text)
    by_kernel = {name.lstrip("%").split(".")[0]: op_name
                 for name, op_name in calls.items()}
    assert set(by_kernel) == {conv_vjp.KERNEL_NAME,
                              pool_bwd.KERNEL_NAME}, calls
    # a backward op's scope reads transpose(jvp(<the layer's scope>))
    assert "jvp(l0_ConvStrictRELU)" in by_kernel[conv_vjp.KERNEL_NAME]
    assert "jvp(l1_MaxPooling)" in by_kernel[pool_bwd.KERNEL_NAME]
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in TOY_SCOPES + ("loss", "update"):
        assert any(re.search(r"[/(]%s[/)]" % scope, name)
                   for name in op_names), scope


def test_loader_gather_is_named_and_scoped(topo, one_chip, mosaic):
    """The loader's gather programs: ``jit_gather_minibatch`` and
    ``jit_gather_labels`` (the names the benchmark's reduction reads)
    with every op under ``loader_gather``."""
    import jax
    rows = aval((4096, 1024), numpy.float32)
    idx = aval((128,), numpy.int32)

    def lowered(fn, *avals):
        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in avals]
        return fn.trace(*avals).lower(lowering_platforms=("tpu",))

    data = lowered(gather.gather_minibatch, rows, idx)
    text = data.compile().as_text()
    assert "HloModule jit_gather_minibatch" in text
    (name, op_name), = mosaic_calls(text).items()
    assert re.match(r"^%veles_gather_rows(\.\d+)?$", name), name
    assert "/loader_gather/" in op_name
    labels = lowered(gather.gather_labels,
                     aval((4096,), numpy.int32), idx)
    text = labels.compile().as_text()
    assert "HloModule jit_gather_labels" in text
    assert "/loader_gather/" in text


def instructions(text):
    """(name, result and operands as written) of each HLO instruction."""
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?(%[\w.-]+) = (.*)", line)
        if found:
            yield found.group(1), re.split(
                r", (?:metadata|backend_config|frontend_attributes)=",
                found.group(2))[0]


def test_mesh_gather_runs_the_kernel_on_each_chips_shard(topo, mosaic):
    """The data-parallel cell's gather (global batch 1,024 from 12,288
    AlexNet rows over the four chips of a v5e host): one program in
    which each chip runs ``veles_gather_rows`` on ITS 3,072 rows, one
    reduce-scatter leaves it its 256 rows of the window, and no chip
    sees the table whole."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(numpy.array(topo.devices), ("data",))
    rows, sample_shape, batch = 12288, (227, 227, 3), 1024
    shape = gather.store_shape(
        rows, int(numpy.prod(sample_shape)), jnp.bfloat16)
    whole = NamedSharding(mesh, PartitionSpec())
    compiled = gather.mesh_gather_minibatch.trace(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=NamedSharding(
            mesh, PartitionSpec("data"))),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=whole),
        mesh=mesh, data_axis="data", out_dtype=numpy.dtype(jnp.bfloat16),
        sample_shape=sample_shape).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "HloModule jit_mesh_gather_minibatch" in text
    (name, op_name), = mosaic_calls(text).items()
    assert re.match(r"^%veles_gather_rows(\.\d+)?$", name), name
    assert "/loader_gather/" in op_name
    lines = dict(instructions(text))
    assert "bf16[3072,1216,128]" in lines[name], lines[name]
    assert not [rest for rest in lines.values()
                if re.match(r"\(?\w+\[%d[,\]]" % rows, rest)]
    exchanges = [rest for rest in lines.values()
                 if re.search(r" (reduce-scatter|all-reduce|all-gather|"
                              r"all-to-all|collective-permute)"
                              r"(-start)?\(", rest)]
    assert len(exchanges) == 1 and re.match(
        r"bf16\[256,1216,128\]\S* reduce-scatter\(", exchanges[0]), exchanges
    assert re.search(r"ENTRY .*-> bf16\[256,227,227,3\]", text)
    # a chip's quarter of the 3.83 GB store, not the store
    held = compiled.memory_analysis().argument_size_in_bytes
    assert held < rows // 4 * 1216 * 128 * 2 + (1 << 20), held


@pytest.mark.parametrize("rows,sample_shape,dtype,out_dtype,batch", [
    (1450000, (784,), "float32", "float32", 100),
    (12288, (227, 227, 3), "bfloat16", "bfloat16", 256),
    (50000, (32, 32, 3), "uint8", "float32", 128),
], ids=["mnist_mlp_f32", "alexnet_bf16", "image_u8_to_f32"])
def test_step_gather_has_no_op_over_the_table(
        topo, one_chip, mosaic, rows, sample_shape, dtype, out_dtype,
        batch):
    """The per-step program on the row store, at both cells' sizes:
    only the parameter and the kernel see the row count as a leading
    dimension (the rule the benchmark's ``data_device_ms_per_step``
    reader applies to a trace), and the store arrives in the layout the
    kernel's ``operand_layout_constraints`` names — no copy between."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    shape = gather.store_shape(
        rows, int(numpy.prod(sample_shape)), dtype)
    assert shape[0] == rows and shape[1] * shape[2] >= numpy.prod(
        sample_shape)
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
             jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)]
    text = gather.gather_minibatch.trace(
        *avals, out_dtype=numpy.dtype(out_dtype),
        sample_shape=sample_shape).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "HloModule jit_gather_minibatch" in text
    over_table = [name for name, rest in instructions(text)
                  if re.match(r"\(?\w+\[%d[,\]]" % rows, rest)]
    assert len(over_table) == 1, over_table  # the parameter
    kernel, = [(name, rest) for name, rest in instructions(text)
               if "tpu_custom_call" in rest]
    assert re.match(r"^%veles_gather_rows(\.\d+)?$", kernel[0])
    assert over_table[0] + ")" in kernel[1], kernel[1]  # its operand
    wanted = re.search(
        r"operand_layout_constraints=\{.*?(\w+\[%d,[\d,]*\])\{([\d,]+)\}"
        % rows, kernel[1])
    assert wanted and wanted.group(2) == "2,1,0", kernel[1]
    entry = re.search(
        r"entry_computation_layout=\{\(%s\{([\d,]+)[:}]"
        % re.escape(wanted.group(1)), text)
    assert entry and entry.group(1) == wanted.group(2), text[:400]


def test_scopes_leave_loss_and_gradients_bit_identical(monkeypatch):
    """CPU, interpreter kernels: the same step traced with
    ``jax.named_scope`` a no-op gives the same bits."""
    import jax
    monkeypatch.setattr(common, "PALLAS_BWD_ENV", "1")
    rng = numpy.random.RandomState(5)
    x = rng.randn(8, *TOY_INPUT).astype(numpy.float32)
    labels = rng.randint(0, 4, 8).astype(numpy.int32)

    def run():
        _, state, step, _ = toy_step()
        new_state, metrics = jax.jit(step)(state, x, labels,
                                           numpy.float32(8))
        return jax.device_get((new_state, metrics))

    scoped = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = run()
    leaves_a, tree_a = jax.tree.flatten(scoped)
    leaves_b, tree_b = jax.tree.flatten(plain)
    assert tree_a == tree_b and len(leaves_a) > 10
    for a, b in zip(leaves_a, leaves_b):
        assert numpy.asarray(a).tobytes() == numpy.asarray(b).tobytes()
    assert numpy.isfinite(scoped[1]["loss"]) and scoped[1]["loss"] > 0


@pytest.mark.parametrize("keeps, forwards", [("nothing", 2), ("names", 1)])
def test_a_recomputed_decoder_layer_runs_the_flash_forward_once(
        topo, one_chip, mosaic, keeps, forwards):
    """A dense decoder layer at the decoder cell's attention widths,
    2,048 tokens, bfloat16, under its checkpoint as the fused step wraps
    it: the bare one replays ``veles_flash_fwd`` in the backward, the
    one that keeps what the kernel named (``attention.KEPT_NAMES``)
    does not, and the compiler drops the replay — not only the jaxpr
    (tests/test_checkpoint_keeps.py)."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.models import decoder
    from veles_tpu.ops import attention
    width, dims = 2048, dict(heads=32, qk_nope=128, qk_rope=64, v_head=128,
                             kv_rank=512, ffn=1024)
    plan = compiler.LayerPlan(decoder.DecoderLayer, static=dict(
        dims, theta=1e6, eps=1e-6, compute_dtype="bfloat16"))
    params = [{name: aval((sum(decoder._size(shape)
                               for _, shape in layout),), "float32")
               for name, layout in zip(
                   ("weights", "bias"),
                   decoder.layer_layout(width, **dims))}]
    remat = attention.KEPT_NAMES if keeps == "names" else True

    def grads(params, h):
        # the loss too, as the step returns it: the forward pass stays
        return jax.value_and_grad(lambda p: compiler._forward_for_loss(
            [plan], p, h, remat=remat).astype(jnp.float32).sum())(params)

    calls = mosaic_calls(compiled_text(
        grads, one_chip, params, aval((1, 2048, width), jnp.bfloat16)))
    kernels = sorted(name.lstrip("%").split(".")[0] for name in calls)
    assert kernels == sorted(
        [attention.FWD_KERNEL_NAME] * forwards
        + [attention.DQ_KERNEL_NAME, attention.DKV_KERNEL_NAME]), calls


def test_the_routed_layer_at_nemotrons_widths_sums_tokens_by_scatters(
        topo, one_chip, mosaic):
    """The ``nemotron_tt`` configuration's routed layer: 16,384 tokens
    2,688 wide, the top 6 of 128 relu² experts 1,856 wide, 8 held,
    bfloat16, forward and backward.  No instruction has the buffer's
    rows (tokens x 6) by the width: each of the two loops over chunks
    adds a chunk's rows to one (N, D) float32 sum by a scatter the
    compiler keeps as a scatter (it sorts the chunk's tokens first), not
    one it expands into a loop over rows."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.models import decoder
    n, width, top_k, held, inner = 16384, 2688, 6, 8, 1856

    def both(m, idx, gate, e_up, e_down, g_out):
        def layer(m, gate, e_up, e_down):
            return decoder.routed_experts(
                m, idx, gate, None, e_up, e_down, first_expert=0,
                capacity=None)[0]
        out, vjp = jax.vjp(layer, m, gate, e_up, e_down)
        return out, vjp(g_out)

    text = compiled_text(
        both, one_chip, aval((n, width), jnp.bfloat16),
        aval((n, top_k), jnp.int32), aval((n, top_k), jnp.float32),
        aval((held, width, inner), jnp.bfloat16),
        aval((held, inner, width), jnp.bfloat16),
        aval((n, width), jnp.bfloat16))
    assert "[%d,%d]" % (n * top_k, width) not in text
    assert len(re.findall(r" while\(", text)) == 2
    sums = re.findall(r"= f32\[%d,%d\]\{[^}]*\} scatter\(" % (n, width), text)
    assert len(sums) == 2, sums
