"""What a layer's checkpoint keeps (docs/kernels.md, "Backward
decongestion"): the flash forward names its output and its two row
statistics (``ops/attention.KEPT_NAMES``), and a step built with
``bwd_remat=KEPT_NAMES`` recomputes each layer but for those — the
forward kernel runs once a layer, the results are the bits of the bare
checkpoint and of the step that keeps every activation, and a layer
that names nothing lowers as under the bare checkpoint.  CPU, the
kernels in the interpreter, the decoder of ``tests/test_decoder.py`` at
toy widths."""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from tests.test_attention_causal import loss_of, operands
from tests.test_decoder import _precision, program_and_batch  # noqa: F401
from veles_tpu.compiler import _forward_for_loss, build_train_step
from veles_tpu.models import decoder, zoo
from veles_tpu.ops import attention
from veles_tpu.ops.attention import KEPT_NAMES, flash_attention

#: every activation, each layer whole again, each layer but for the names
HOLDS = {"activations": False, "nothing": True, "named": KEPT_NAMES}

#: XLA keeps a bfloat16 fusion's intermediates wider than stored, and
#: which ops share a fusion differs between the three programs: with
#: that off, they compute the same bits in either precision
EXACT = {"xla_allow_excess_precision": False}


def flash_decoder(layers):
    """The toy decoder's plans with the attention through the flash
    kernels (the CPU's default is the stock reference), its state and
    one minibatch."""
    _, _, plans, state, x, y = program_and_batch(layers=layers)
    for plan in plans:
        if plan.forward_cls is decoder.DecoderLayer:
            plan.static["pallas_bwd"] = True
    return plans, state, x, y


def subjaxprs(params):
    for value in params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def kernel_calls(jaxpr, found=None):
    """{kernel name: ``pallas_call``s} of a jaxpr and all it encloses."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        else:
            for inner in subjaxprs(eqn.params):
                kernel_calls(inner, found)
    return found


@pytest.mark.parametrize("layers", [1, 2])
def test_the_forward_kernel_runs_once_a_layer(_precision, layers):
    plans, state, x, y = flash_decoder(layers)

    def calls(remat):
        step = build_train_step(plans, donate=False, bwd_remat=remat)
        return kernel_calls(jax.make_jaxpr(functools.partial(
            step, step_count=numpy.int32(1)))(
                state, x, y, numpy.float32(4)).jaxpr)

    backward = {attention.DQ_KERNEL_NAME: layers,
                attention.DKV_KERNEL_NAME: layers}
    assert calls(KEPT_NAMES) == dict(
        backward, **{attention.FWD_KERNEL_NAME: layers})
    assert calls(False) == calls(KEPT_NAMES)
    assert calls(True) == dict(
        backward, **{attention.FWD_KERNEL_NAME: 2 * layers})


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_the_three_steps_are_the_same_bits(_precision, precision):
    """Loss, every gradient (AdamW's first moment after step 1 is
    0.1 g) and the updated state."""
    _precision(precision)
    plans, state, x, y = flash_decoder(2)
    results = {
        name: jax.device_get(build_train_step(
            plans, donate=False, bwd_remat=remat, compiler_options=EXACT)(
                state, x, y, numpy.float32(4), step_count=numpy.int32(1)))
        for name, remat in HOLDS.items()}
    named, tree = jax.tree.flatten(results["named"])
    assert len(named) > 20 and numpy.isfinite(
        results["named"][1]["loss"])
    for other in ("nothing", "activations"):
        leaves, other_tree = jax.tree.flatten(results[other])
        assert other_tree == tree
        for a, b in zip(named, leaves):
            assert a.dtype == b.dtype
            assert numpy.asarray(a).tobytes() == numpy.asarray(b).tobytes()
    moved = [numpy.abs(new["weights"] - numpy.asarray(old["weights"])).max()
             for new, old in zip(results["named"][0], state)]
    assert min(moved) > 0


def saved_residuals(capsys, fn, *args):
    """[(dtype, shape, what)] from ``print_saved_residuals``' lines."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    found = []
    for line in capsys.readouterr().out.splitlines():
        dtype, dims, what = re.match(
            r"(\w+)\[([\d,]*)\] (.*)", line).groups()
        found.append((dtype, tuple(int(d) for d in dims.split(",") if d),
                      what))
    return found


def test_a_layer_saves_its_output_and_two_floats_a_row(_precision, capsys):
    plans, state, x, _ = flash_decoder(1)
    (embedding, layer, _), (table, params, _) = plans, state
    h = embedding.forward_cls.apply(table, x, **embedding.static)
    batch, tokens, _ = h.shape
    rows = batch * layer.static["heads"]
    apply = functools.partial(layer.forward_cls.apply, **layer.static)

    def saved(policy):
        kept = saved_residuals(
            capsys, lambda p, h_: jax.checkpoint(apply, policy=policy)(
                p, h_).sum(), params, h)
        return sorted((dtype, shape) for dtype, shape, what in kept
                      if not re.match("from (the argument|a constant)",
                                      what)), kept

    assert saved(None)[0] == []
    inside, lines = saved(
        jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))
    assert inside == [("f32", (rows, tokens)), ("f32", (rows, tokens)),
                      ("f32", (rows, tokens, layer.static["v_head"]))]
    # by name too, where jax prints one: the statistics; the output
    # goes on into w_o's product, and is listed as that use's rounding
    assert sorted(re.findall(r"named '(\w+)'", " ".join(
        what for _, _, what in lines))) == sorted(KEPT_NAMES[1:])
    # as the decision (FusedTrainer._backward_should_recompute) sizes
    # them: what the names add to a recomputed layer's inputs
    fwd = lambda remat: nbytes(jax.eval_shape(  # noqa: E731
        lambda p, x_: jax.vjp(lambda q: _forward_for_loss(
            plans, q, x_, remat=remat), p)[1], state, x))
    assert fwd(KEPT_NAMES) - fwd(True) == 4 * rows * tokens * (
        layer.static["v_head"] + 2)
    assert fwd(False) > fwd(KEPT_NAMES)


def nbytes(tree):
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


TOYS = {
    "conv": ([
        {"type": "conv_str", "n_kernels": 4, "kx": 3, "ky": 3,
         "padding": 1, "learning_rate": 0.05, "gradient_moment": 0.9},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "softmax", "output_sample_shape": 5,
         "learning_rate": 0.05, "gradient_moment": 0.9}], (12, 12, 3)),
    "dense": (zoo.mnist_mlp_layers(hidden=16), (28, 28)),
}


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_layers_that_name_nothing_lower_as_under_the_bare_checkpoint(toy):
    specs, shape = TOYS[toy]
    plans, state, _ = zoo.build_plans_and_state(specs, shape, seed=2)
    x = numpy.zeros((8,) + shape, numpy.float32)
    labels = numpy.zeros((8,), numpy.int32)

    def text(remat):
        return build_train_step(plans, donate=False, bwd_remat=remat).lower(
            state, x, labels, numpy.float32(8)).as_text()

    assert text(KEPT_NAMES) == text(True)
    assert text(False) != text(True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_outside_a_checkpoint_a_name_is_an_identity(dtype):
    """The output is the kernel's, the statistics lose nothing by
    leaving 127 of their 128 lanes behind, and the gradients are the
    backward kernels' on the kernel's own statistics: what
    ``flash_attention`` returned before it named anything."""
    q, k, v = operands(7, 2, 300, 48, 32, jnp.dtype(dtype))
    narrow = None if dtype == "float32" else dtype
    form = dict(causal=True, product_dtype=narrow)
    static = (1.0 / math.sqrt(48), 0, (104, 128), True)
    out, stats = attention._flash_fwd_jit(q, k, v, *static, **form)
    for stat in stats:
        assert stat.shape == (2, 312, 128) and stat.dtype == jnp.float32
        assert (stat == stat[:, :, :1]).all()
    flash = functools.partial(flash_attention, blocks=(104, 128), **form)
    assert numpy.asarray(flash(q, k, v)).tobytes() == \
        numpy.asarray(out).tobytes()
    loss = loss_of(flash)
    do = jax.grad(lambda o: loss_of(lambda *_: o)(q, k, v))(out)
    wanted = attention._flash_bwd_jit(
        q, k, v, out, tuple(stat[:, :, 0] for stat in stats), do,
        *static, **form)
    for name, policy in (
            ("bare", None), ("jit", None), ("checkpoint", None),
            ("names", jax.checkpoint_policies.save_only_these_names(
                *KEPT_NAMES))):
        fn = {"bare": loss, "jit": jax.jit(loss)}.get(
            name, jax.checkpoint(loss, policy=policy))
        grads = jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(grads, wanted):
            assert numpy.asarray(got).tobytes() == \
                numpy.asarray(want).tobytes(), name
