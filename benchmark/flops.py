"""Operations and bytes one train step needs, counted from the layer
specs and the shapes (XLA's cost model reports no FLOPs for the fused
step on a TPU), and the floor they set on a chip of ``peaks.json``.

Operations: 2 per multiply-add; forward, weight gradient and input
gradient each cost the forward's, except that the first layer with
weights needs no input gradient (``need_err_input`` is false there), so
it is not counted.  Recomputation, activations, pooling, the loss and
the solver's update are not counted.

Bytes, the least a step must move through HBM: the minibatch read once,
every activation written forward and read backward, every parameter and
its momentum read and written once.
"""

import json
import os

import numpy

HERE = os.path.dirname(os.path.abspath(__file__))
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def padding4(value):
    """(left, top, right, bottom) from an int, a pair or four."""
    if isinstance(value, int):
        return (value,) * 4
    if len(value) == 2:
        return (value[0], value[1], value[0], value[1])
    return tuple(value)


def pooled_length(length, window, stride):
    """Outputs of a pooling that covers the whole input: a partial
    window at the edge counts."""
    if length <= window:
        return 1
    return -(-(length - window) // stride) + 1


def layer_costs(layers, input_shape):
    """[(type, multiply-adds per image forward, parameters, outputs per
    image)] walking ``layers`` from ``input_shape`` (no batch)."""
    shape = tuple(input_shape)
    rows = []
    for spec in layers:
        kind = spec["type"]
        if kind.startswith("conv"):
            h, w = shape[0], shape[1]
            ch = shape[2] if len(shape) > 2 else 1
            left, top, right, bottom = padding4(spec.get("padding", 0))
            sx, sy = spec.get("sliding", (1, 1))
            ky, kx, n = spec["ky"], spec["kx"], spec["n_kernels"]
            shape = ((h + top + bottom - ky) // sy + 1,
                     (w + left + right - kx) // sx + 1, n)
            weights = ky * kx * ch * n
            rows.append((kind, shape[0] * shape[1] * weights,
                         weights + n, int(numpy.prod(shape))))
        elif kind.endswith("pooling"):
            ky, kx = spec["ky"], spec["kx"]
            sx, sy = spec.get("sliding", (kx, ky))
            shape = (pooled_length(shape[0], ky, sy), pooled_length(shape[1], kx, sx),
                     shape[2] if len(shape) > 2 else 1)
            rows.append((kind, 0, 0, int(numpy.prod(shape))))
        elif kind == "dropout":
            rows.append((kind, 0, 0, int(numpy.prod(shape))))
        elif kind.startswith("all2all") or kind == "softmax":
            inputs = int(numpy.prod(shape))
            outputs = int(numpy.prod(spec["output_sample_shape"]))
            shape = (outputs,)
            rows.append((kind, inputs * outputs, inputs * outputs + outputs,
                         outputs))
        else:
            raise ValueError("flops.py cannot count a %r layer" % kind)
    return rows


def train_flops_per_image(layers, input_shape):
    """Forward and backward, as the module's docstring counts them."""
    macs = [row[1] for row in layer_costs(layers, input_shape)]
    first = next((m for m in macs if m), 0)
    return 6 * sum(macs) - 2 * first


def train_bytes_per_step(layers, input_shape, batch, dtype):
    size = ITEMSIZE[dtype]
    rows = layer_costs(layers, input_shape)
    inputs = int(numpy.prod(input_shape))
    activations = sum(row[3] for row in rows)
    parameters = sum(row[2] for row in rows)
    return size * (batch * inputs + 2 * batch * activations
                   + 4 * parameters)


def peaks(device_kind):
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as fin:
        table = json.load(fin)
    if device_kind not in table:
        raise KeyError("peaks.json has no entry for device kind %r (it "
                       "has %s)" % (device_kind, sorted(table)))
    return table[device_kind]


def floor_seconds(flops, nbytes, peak, dtype, chips=1):
    """(seconds, "compute" | "bytes"): the least time ``chips`` chips
    of ``peak`` could take over ``flops`` and ``nbytes``."""
    rate = peak["flops_per_s"].get(dtype)
    if rate is None:
        raise KeyError("peaks.json gives no FLOP/s for %r" % dtype)
    compute = flops / (rate * chips)
    memory = nbytes / (peak["bytes_per_s"] * chips)
    return (compute, "compute") if compute >= memory else (memory, "bytes")


def step_cost(config, batch):
    """{"flops", "bytes", "flops_per_image"} of one train step of
    ``config`` (a configuration file's contents) at ``batch``."""
    from veles_tpu.models import zoo
    model = config["model"]
    layers = getattr(zoo, model["factory"])(**model.get("arguments", {}))
    per_image = train_flops_per_image(layers, config["input_shape"])
    return {"flops_per_image": per_image, "flops": per_image * batch,
            "bytes": train_bytes_per_step(
                layers, config["input_shape"], batch, config["dtype"])}
