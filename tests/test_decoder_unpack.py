"""A decoder layer's packed vectors cut piece by piece
(``decoder._unpacker``): each piece is sliced from the float32 vector
before it is reshaped or cast, so the compiled unpack holds no cast and
no relayout of the WHOLE vector (PERF.md section 7, row 1c: the compiler
had moved each reshape ahead of its slice, one whole-vector relayout a
distinct minor width).

Compiled for a DESCRIBED v5e at the published widths of every layer kind
of the three decoder cells (the on-chip-measurement guide, section 2:
the topology is described inside a module-scoped fixture, never at
import); on the CPU at toy widths, the pieces are the parent's bits and
the gradient is ONE concatenate of the pieces'."""

import re

import numpy
import pytest

from veles_tpu.models import decoder

#: each layer kind of the accepted decoder configurations at the
#: published widths (benchmark/configs/*.json), hidden 2,048
KANANA = dict(heads=32, qk_nope=128, qk_rope=64, v_head=128, kv_rank=512)
TRINITY = dict(heads=32, kv_heads=4, head_width=128, post_norms=True)
LFM2_ROUTED = dict(experts=32, experts_held=8, expert_width=1792,
                   shared_width=0)
PUBLISHED = {
    "lfm2_dense": dict(conv_taps=3, ffn=7168),
    "lfm2_conv_routed": dict(conv_taps=3, **LFM2_ROUTED),
    "lfm2_attention_routed": dict(heads=32, kv_heads=8, head_width=64,
                                  out_gate=False, **LFM2_ROUTED),
    "kanana_dense": dict(KANANA, ffn=6144),
    "kanana_routed": dict(KANANA, experts=128, experts_held=16,
                          expert_width=768, shared_width=1536),
    "trinity_dense": dict(TRINITY, ffn=6144),
    "trinity_routed": dict(TRINITY, experts=128, experts_held=8,
                           expert_width=1024, shared_width=1024),
}
#: the same kinds at toy widths, hidden 64
TOY = {
    "lfm2_dense": dict(conv_taps=3, ffn=96),
    "lfm2_conv_routed": dict(conv_taps=3, experts=8, experts_held=4,
                             expert_width=48, shared_width=0),
    "lfm2_attention_routed": dict(heads=4, kv_heads=2, head_width=16,
                                  out_gate=False, experts=8,
                                  experts_held=4, expert_width=48,
                                  shared_width=0),
    "kanana_dense": dict(heads=4, qk_nope=16, qk_rope=8, v_head=16,
                         kv_rank=24, ffn=96),
    "kanana_routed": dict(heads=4, qk_nope=16, qk_rope=8, v_head=16,
                          kv_rank=24, experts=16, experts_held=4,
                          expert_width=32, shared_width=64),
    "trinity_dense": dict(heads=4, kv_heads=2, head_width=16,
                          post_norms=True, ffn=96),
    "trinity_routed": dict(heads=4, kv_heads=2, head_width=16,
                           post_norms=True, experts=16, experts_held=4,
                           expert_width=32, shared_width=32),
}


def _length(layout):
    return sum(decoder._size(shape) for _, shape in layout)


def _result_shapes(text):
    """(opcode, [element counts of its result]) of each instruction of
    ``compiled.as_text()``, fused computations included."""
    for line in text.splitlines():
        if " = " not in line or not line.lstrip().startswith(("%", "ROOT")):
            continue
        rhs = line.split(" = ", 1)[1]
        op = re.search(r" ([a-z][\w.-]*)\(", rhs)
        if op is None:
            continue
        result = rhs[:op.start()]
        if op.group(1).endswith("-start"):
            # an async op's tuple opens with its operands, by alias
            result = result.split("), ", 1)[-1]
        yield op.group(1), [
            int(numpy.prod([int(d) for d in dims.split(",") if d]))
            for _, dims in re.findall(r"(\w+)\[([\d,]*)\]", result)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % exc)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def silent_cache():
    """A deviceless compile can be written to the persistent cache but
    not read back: keep the cache off around it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", sorted(PUBLISHED))
def test_the_compiled_unpack_never_relays_the_whole_vector(
        one_chip, silent_cache, kind):
    """No instruction but the parameter holds as many elements as the
    packed vector (no whole-vector cast, no whole-vector relayout at any
    width), and the bytes XLA's cost model gives are at most 1.5 x one
    read of 4 B and one write of 2 B a parameter."""
    import jax
    import jax.numpy as jnp
    layout, _ = decoder.layer_layout(2048, **PUBLISHED[kind])
    length = _length(layout)
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda v: decoder.unpack(v, layout, jnp.bfloat16)).trace(
            vec).lower(lowering_platforms=("tpu",)).compile()
    whole = sorted({op for op, counts in _result_shapes(compiled.as_text())
                    if length in counts and op != "parameter"})
    assert not whole, whole
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 1.5 * 6 * length, (
        cost["bytes accessed"] / (6 * length))


@pytest.mark.parametrize("kind", sorted(TOY))
def test_the_pieces_are_the_slices_bit_for_bit(kind):
    """At toy widths on the CPU, eager and under jit: each piece is
    ``vec[o:o + s].reshape(shape).astype(dtype)``, weights in bfloat16
    (the router's in float32) and gains in float32; the vjp is the
    pieces' cotangents raveled in float32 and concatenated, as the
    parent's ``bwd`` gave."""
    import jax
    import jax.numpy as jnp
    rng = numpy.random.RandomState(38)
    for layout, dtype in zip(decoder.layer_layout(64, **TOY[kind]),
                             (jnp.bfloat16, jnp.float32)):
        vec = jnp.asarray(rng.normal(size=_length(layout)), jnp.float32)
        want, offset = {}, 0
        for name, shape in layout:
            size = decoder._size(shape)
            want[name] = vec[offset:offset + size].reshape(shape).astype(
                jnp.float32 if name in decoder.FLOAT32_PIECES else dtype)
            offset += size
        for unpack in (decoder.unpack, jax.jit(decoder.unpack,
                                               static_argnums=(1, 2))):
            got = unpack(vec, tuple(layout), dtype)
            assert sorted(got) == sorted(want)
            for name, piece in got.items():
                assert piece.dtype == want[name].dtype, name
                assert piece.shape == want[name].shape, name
                assert numpy.asarray(piece).tobytes() == numpy.asarray(
                    want[name]).tobytes(), name
        cotangents = {name: jnp.asarray(rng.normal(size=piece.shape),
                                        piece.dtype)
                      for name, piece in want.items()}
        _, vjp = jax.vjp(lambda v: decoder.unpack(v, layout, dtype), vec)
        grad, = vjp(cotangents)
        expected = jnp.concatenate([
            cotangents[name].astype(jnp.float32).ravel()
            for name, _ in layout])
        assert grad.dtype == jnp.float32
        assert numpy.asarray(grad).tobytes() == numpy.asarray(
            expected).tobytes()
