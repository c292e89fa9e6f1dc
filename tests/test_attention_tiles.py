"""The causal flash kernels' three tile classes (interpret mode): a
grid step is skipped, runs the unmasked body (a WHOLE tile: every pair
kept, no padded key) or the masked one (an EDGE).  The unmasked body
must change no bit — the select it leaves out returns its first
operand there — so the kernels are compared, bit for bit, with
themselves under a helper that calls no tile whole, which is the
arithmetic of ATTENTION_KERNEL_VERSION 4.  How often each class runs is
static: ``tile_census`` against a count over the boolean mask.  Only
the forward uses the class — the mask is free on the chip, and what
the forward gains is the boundary of the ``lax.cond`` its mask sits
under — and only while the branch that hands the scores on is the TRUE
one: read from the jaxpr and, compiled for a described v5e, from
Mosaic's canonicalized ``scf.if``."""

import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy
import pytest

from tests.test_attention_causal import loss_of
from tests.test_attention_window import operands
from veles_tpu.ops import attention
from veles_tpu.ops.attention import flash_attention, tile_census


def _forget_traces():
    attention._flash_fn.cache_clear()
    attention._flash_fwd_jit.clear_cache()
    attention._flash_bwd_jit.clear_cache()


@contextlib.contextmanager
def patched(monkeypatch, **names):
    """``attention``'s names replaced, for programs traced inside."""
    with monkeypatch.context() as patch:
        for name, value in names.items():
            patch.setattr(attention, name, value)
        _forget_traces()
        yield
    _forget_traces()


TILE_CLASSES = attention._tile_classes


def no_tile_is_whole(*place):
    """Every needed tile an edge: version 4's two classes."""
    needed, whole = TILE_CLASSES(*place)
    return needed, whole & False


#: (b, group, t, key width, value width, dtype, level, product_dtype,
#: window, blocks)
CASES = {
    "latent_192_128_bfloat16_products": (
        2, 1, 512, 192, 128, jnp.bfloat16, 0, jnp.bfloat16, None,
        (128, 128)),
    "grouped_full": (
        4, 4, 512, 128, 128, jnp.bfloat16, 0, jnp.bfloat16, None,
        (128, 128)),
    "grouped_window_a_multiple_of_bk": (
        4, 4, 512, 128, 128, jnp.bfloat16, 0, jnp.bfloat16, 256,
        (128, 128)),
    "grouped_window_no_multiple_of_bk": (
        4, 4, 512, 128, 128, jnp.bfloat16, 0, jnp.bfloat16, 300,
        (128, 128)),
    "float32_level_0": (
        2, 1, 384, 64, 48, jnp.float32, 0, None, None, (128, 128)),
    "float32_level_1": (
        2, 2, 384, 64, 48, jnp.float32, 1, None, 300, (128, 128)),
    "ragged_last_key_tile": (
        2, 1, 300, 64, 48, jnp.float32, 1, None, None, (104, 128)),
    "ragged_windowed": (
        2, 2, 600, 64, 48, jnp.float32, 1, None, 400, (104, 128)),
    "q_tiles_wider_than_k_tiles": (
        2, 1, 512, 64, 64, jnp.float32, 1, None, None, (256, 128)),
    "k_tiles_wider_than_q_tiles": (
        2, 2, 512, 64, 64, jnp.float32, 1, None, 384, (64, 256)),
}


#: the interpreter's kernel bodies are XLA:CPU's to fuse, and which ops
#: share a fusion differs with and without the select between them: a
#: fusion keeps bfloat16 intermediates wider than stored, contracts a
#: product and a difference it holds into one rounding, and sums a row
#: in its own order (bfloat16 row sums differed in the last float32 bit
#: with fusion on).  Op by op, the two programs do the same arithmetic
EXACT = {"xla_allow_excess_precision": False,
         "xla_disable_hlo_passes": "fusion"}


def everything_of(case):
    """(out, row max, row sum, dq, dk, dv) as the kernels give them."""
    b, group, t, dk, dv, dtype, level, product, window, blocks = case
    q, k, v = operands(t + b, b, group, t, dk, dv, dtype)
    form = dict(causal=True, window=window, product_dtype=(
        None if product is None else jnp.dtype(product).name))

    def everything(q, k, v):
        out, (row_max, row_sum) = attention._flash_fwd_jit(
            q, k, v, dk ** -0.5, level, blocks, True, **form)
        grads = jax.grad(loss_of(lambda *a: flash_attention(
            *a, precision_level=level, blocks=blocks, causal=True,
            window=window, product_dtype=product)),
            argnums=(0, 1, 2))(q, k, v)
        return (out, row_max, row_sum) + grads

    return jax.jit(everything).lower(q, k, v).compile(
        compiler_options=EXACT)(q, k, v)


def bits(array):
    return numpy.asarray(array).tobytes()


@pytest.mark.parametrize("name", sorted(CASES))
def test_whole_tiles_change_no_bit(name, monkeypatch):
    case = CASES[name]
    t, window, blocks = case[2], case[8], case[9]
    whole, edge, _ = tile_census(t, *attention._clamped_blocks(blocks, t),
                                 window)
    assert whole and edge, "the case has to run both bodies"
    mine = everything_of(case)
    with patched(monkeypatch, _tile_classes=no_tile_is_whole):
        assert tile_census(t, *blocks, window)[0] == 0
        masked_everywhere = everything_of(case)
    for got, want, what in zip(mine, masked_everywhere,
                               ("out", "row max", "row sum", "dq", "dk",
                                "dv")):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert numpy.isfinite(numpy.asarray(got, numpy.float32)).all()
        assert bits(got) == bits(want), what


def test_a_padded_last_key_tile_stays_an_edge(monkeypatch):
    """300 tokens in 128-wide key tiles: the third holds 84 padded
    keys, so no step on it is whole, and — read before the unpad — its
    padded dk and dv rows are exact zeros."""
    t, (bq, bk) = 300, (104, 128)
    for i in range(-(-t // bq)):
        assert TILE_CLASSES(i, 2, bq, bk, None, t) == (i == 2, False)
    # and a tile of real keys below the diagonal is whole
    assert TILE_CLASSES(2, 0, bq, bk, None, t) == (True, True)
    q, k, v = operands(3, 2, 1, t, 64, 48)
    with patched(monkeypatch, unpad=lambda padded, shape: padded):
        out, (row_max, row_sum) = attention._flash_fwd_jit(
            q, k, v, 0.125, 1, (bq, bk), True, causal=True)
        stats = (row_max[:, :, 0], row_sum[:, :, 0])
        _, dk, dv = attention._flash_bwd_jit(
            q, k, v, out[:, :t, :48], stats, jnp.ones_like(v), 0.125, 1,
            (bq, bk), True, causal=True)
    assert dk.shape == (2, 384, 128) and dv.shape == (2, 384, 128)
    assert numpy.abs(numpy.asarray(dk[:, :t])).max() > 0
    assert not numpy.asarray(dk[:, t:]).any()
    assert not numpy.asarray(dv[:, t:]).any()


def counted_over_the_mask(t, bq, bk, window):
    """(whole, edge, skipped) of the forward's grid steps, from the
    boolean mask of ``attention_reference`` laid over the padded
    extent: a padded key is never kept, a padded query row follows the
    same rule as a real one (the kernels mask it so, and slice it
    away)."""
    n_q, n_k = -(-t // bq), -(-t // bk)
    rows, cols = numpy.arange(n_q * bq), numpy.arange(n_k * bk)
    back = rows[:, None] - cols[None, :]
    keep = (back >= 0) & (cols < t)[None, :]
    if window is not None:
        keep &= back < window
    counts = [0, 0, 0]
    steps = n_k if window is None else attention._band_steps(
        t, bq, bk, window)[0]
    for i in range(n_q):
        first = 0 if window is None else max(i * bq - window + 1, 0) // bk
        for kk in range(first, first + steps):
            tile = keep[i * bq:(i + 1) * bq, kk * bk:(kk + 1) * bk]
            counts[0 if tile.size and tile.all() else
                   1 if tile.any() else 2] += 1
    return tuple(counts)


@pytest.mark.parametrize("t, bq, bk, window", [
    (8192, 512, 512, None), (8192, 512, 512, 2048),
    (512, 128, 128, None), (512, 128, 128, 256), (512, 128, 128, 300),
    (384, 128, 128, 300), (300, 104, 128, None), (300, 104, 128, 150),
    (300, 104, 128, 40), (600, 104, 128, 400), (512, 256, 128, None),
    (512, 64, 256, 384),
    (1000, 128, 384, 129), (2048, 512, 512, 1), (512, 512, 512, None)])
def test_census_against_a_count_over_the_mask(t, bq, bk, window):
    census = tile_census(t, bq, bk, window)
    assert census == counted_over_the_mask(t, bq, bk, window)
    steps = -(-t // bk) if window is None else attention._band_steps(
        t, bq, bk, window)[0]
    assert sum(census) == -(-t // bq) * steps


def test_census_of_the_two_cells():
    """8,192 tokens in (512, 512) tiles: of a head's 136 visited tiles
    120 are whole; under the 2,048-token window 42 of the band's 70."""
    assert tile_census(8192, 512, 512) == (120, 16, 120)
    assert tile_census(8192, 512, 512, 2048) == (42, 28, 10)


def primitives_in(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            primitives_in(inner, found)
    return found


def tile_bodies(jaxpr, found=None):
    """The jaxpr of every ``cond`` branch of a kernel that multiplies
    matrices: the bodies that run on a tile."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.extend(
                branch.jaxpr for branch in eqn.params["branches"]
                if "dot_general" in primitives_in(branch.jaxpr))
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                tile_bodies(inner, found)
    return found


def kernels_of(jaxpr, found=None):
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["jaxpr"]
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                kernels_of(inner, found)
    return found


def traced_grads(t, window, causal=True, blocks=(128, 128)):
    q = jax.ShapeDtypeStruct((4, t, 64), jnp.float32)
    return jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, blocks=blocks, causal=causal, window=window).sum(),
        argnums=(0, 1, 2)))(q, q, q).jaxpr


@pytest.mark.parametrize("window", [None, 256])
def test_the_forward_hands_a_whole_tile_on_in_the_true_branch(window):
    """One body a kernel.  The backward kernels' is masked, as it was;
    in the forward's the mask and nothing else sits under a ``cond``
    on the WHOLE predicate itself, no negation of it, whose TRUE branch
    hands the scores on as they are: Mosaic makes that the ``then`` of
    an ``scf.if``, and the other way around the tile is copied and the
    forward's gain gone (docs/kernels.md)."""
    kernels = kernels_of(traced_grads(512, window))
    names = ((attention.WIN_FWD_KERNEL_NAME, attention.WIN_DQ_KERNEL_NAME,
              attention.WIN_DKV_KERNEL_NAME) if window else
             (attention.FWD_KERNEL_NAME, attention.DQ_KERNEL_NAME,
              attention.DKV_KERNEL_NAME))
    assert sorted(kernels) == sorted(names)
    for name in names[1:]:
        body, = tile_bodies(kernels[name])
        assert "cond" not in primitives_in(body), name
        assert {"iota", "select_n"} < primitives_in(body), name
    body, = tile_bodies(kernels[names[0]])
    fork, = [eqn for eqn in body.eqns if eqn.primitive.name == "cond"]
    assert not {"iota", "select_n"} & {eqn.primitive.name
                                         for eqn in body.eqns}
    # ``cond``'s branches are indexed by the predicate: [false, true]
    masked, kept = (branch.jaxpr for branch in fork.params["branches"])
    assert not kept.eqns and kept.outvars == kept.invars[-1:]
    assert {"iota", "select_n"} < primitives_in(masked)
    # the index is the predicate as an integer, and the predicate a
    # conjunction of compares: no ``not`` anywhere on its way
    made = {var: eqn for eqn in body.eqns for var in eqn.outvars}
    index = made[fork.invars[0]]
    assert index.primitive.name == "convert_element_type"
    seen, todo = set(), [index.invars[0]]
    while todo:
        eqn = made.get(todo.pop())
        if eqn is not None:
            seen.add(eqn.primitive.name)
            todo.extend(var for var in eqn.invars
                        if isinstance(var, jax.extend.core.Var))
    assert "and" in seen and "not" not in seen, seen


def test_the_plain_form_has_one_body_and_it_is_masked():
    """Not causal, any tile may hold padded keys and nothing else is
    masked: one body, run at every step, as it was."""
    kernels = kernels_of(traced_grads(300, None, causal=False,
                                      blocks=(104, 128)))
    assert len(kernels) == 3
    for name, kernel in kernels.items():
        assert tile_bodies(kernel) == [], name
        assert {"iota", "dot_general"} < primitives_in(kernel), name


SCF_IF_PROBE = """
import glob, os, sys
os.environ["LIBTPU_INIT_ARGS"] = "--xla_mosaic_dump_to=" + sys.argv[1]
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from veles_tpu.ops import attention, common
common.interpret_mode = lambda: False
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as exc:
    print("NO TOPOLOGY", exc)
    sys.exit(0)
chip = SingleDeviceSharding(topo.devices[0])
window = int(sys.argv[2]) or None
q, v = (jax.ShapeDtypeStruct((2, 2048, width), jnp.bfloat16, sharding=chip)
        for width in (192, 128))
jax.jit(lambda *a: attention.flash_attention(
    *a, causal=True, window=window, product_dtype=jnp.bfloat16)).trace(
        q, q, v).lower(lowering_platforms=("tpu",)).compile()
print("COMPILED")
"""


@pytest.mark.parametrize("window", [0, 1024])
def test_mosaic_keeps_the_whole_tile_in_the_then_region(window, tmp_path):
    """What the chip's compiler makes of the forward's ``cond``, read
    from the MLIR a deviceless compile for a described v5e dumps after
    Mosaic's canonicalization (a process of its own: libtpu reads the
    flag once): ONE ``scf.if`` yields a score tile, its ``then`` region
    is that yield alone, and the mask's select is in its ``else``.  A
    ``then`` with the mask in it — a negated predicate, branches
    swapped, a jax or libtpu that lowers ``cond`` otherwise — read
    24.0 ms a call against 21.3 on the chip (PR 34)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", SCF_IF_PROBE, str(tmp_path), str(window)],
        cwd=root, text=True, capture_output=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=root, TPU_LOG_DIR="disabled"))
    assert done.returncode == 0, done.stderr[-2000:]
    if "NO TOPOLOGY" in done.stdout:
        pytest.skip("no v5e:2x2 topology can be described here")
    name = (attention.WIN_FWD_KERNEL_NAME if window
            else attention.FWD_KERNEL_NAME)
    dumps = sorted(tmp_path.glob(
        "*-mosaic-dump-%s-post-canonicalize-mosaic-simplify.txt" % name))
    if not dumps:
        pytest.skip("this libtpu dumps no canonicalized MLIR")
    lines = [line.strip() for line in dumps[-1].read_text().splitlines()]
    forks = [n for n, line in enumerate(lines)
             if re.match(r"%\w+ = scf\.if %\w+ -> \(vector<\d+x\d+xf32>\)",
                         line)]
    assert len(forks) == 1, forks
    then = lines[forks[0] + 1:lines.index("} else {", forks[0])]
    assert len(then) == 1 and then[0].startswith("scf.yield %"), then
    closes = lines.index("}", forks[0])
    assert any("arith.select" in line
               for line in lines[forks[0] + 3:closes])
