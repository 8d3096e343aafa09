"""The plain reference a cell's outputs are judged against, and the
benchmark's own model arithmetic.

Everything here reads a configuration file (``configs/<name>.json``) and
nothing of the program under test: the layer list, the shapes inferred from
it, the seeded weights and inputs, the FLOP count and the forward pass in
straightforward ``jax.numpy`` / ``lax``.

A configuration's ``layers`` are ``[name, op, inputs, attrs]`` rows in
topological order after the implicit ``input`` layer:

* ``conv``: ``features``, ``kernel``, ``stride`` (default 1); SAME padding,
  bias, ReLU.
* ``maxpool`` / ``avgpool``: ``kernel``, ``stride``; SAME padding (maxpool
  pads with -inf; avgpool divides each window sum by ``kernel**2``).
* ``concat``: channel concatenation of its inputs.
* ``flatten``: one feature vector per sample.
* ``dense``: ``features``, ``relu`` (default true); bias.

The forward pass comes in three precisions: ``"highest"`` (float32
products, the reference) and two controls one step below it. ``"high"``
splits each float32 operand into a bfloat16 high part and a bfloat16
remainder and accumulates three bfloat16 products in float32, dropping the
remainder-by-remainder product: the three-pass scheme written out, so it
reads the same on the CPU as on the chip. ``"high_chip"`` asks the backend
for ``Precision.HIGH`` itself, which the TPU computes in three passes and
the CPU ignores.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PRECISIONS = ("highest", "high", "high_chip")
_DN = ("NHWC", "HWIO", "NHWC")


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def layer_shapes(cfg: Mapping) -> Dict[str, Tuple[int, ...]]:
    """Per-sample output shape of every layer, ``input`` included."""
    shapes: Dict[str, Tuple[int, ...]] = {"input": tuple(cfg["input_shape"])}
    for name, op, ins, a in cfg["layers"]:
        src = [shapes[i] for i in ins]
        if op == "conv":
            h, w, _ = src[0]
            s = a.get("stride", 1)
            shapes[name] = (_same_out(h, s), _same_out(w, s), a["features"])
        elif op in ("maxpool", "avgpool"):
            h, w, c = src[0]
            s = a["stride"]
            shapes[name] = (_same_out(h, s), _same_out(w, s), c)
        elif op == "concat":
            shapes[name] = (*src[0][:-1], sum(x[-1] for x in src))
        elif op == "flatten":
            shapes[name] = (int(np.prod(src[0])),)
        elif op == "dense":
            shapes[name] = (a["features"],)
        else:
            raise ValueError(f"layer {name}: unknown op {op!r}")
    return shapes


def param_shapes(cfg: Mapping) -> Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """``name -> (weight shape, bias shape)`` of every conv and dense layer."""
    shapes = layer_shapes(cfg)
    out = {}
    for name, op, ins, a in cfg["layers"]:
        if op == "conv":
            k, cin = a["kernel"], shapes[ins[0]][-1]
            out[name] = ((k, k, cin, a["features"]), (a["features"],))
        elif op == "dense":
            out[name] = ((shapes[ins[0]][0], a["features"]), (a["features"],))
    return out


def flops_per_inference(cfg: Mapping) -> float:
    """Two FLOPs per multiply-add of the conv and dense layers, one sample."""
    shapes = layer_shapes(cfg)
    total = 0
    for name, op, ins, a in cfg["layers"]:
        if op == "conv":
            ho, wo, cout = shapes[name]
            total += 2 * ho * wo * a["kernel"] ** 2 * shapes[ins[0]][-1] * cout
        elif op == "dense":
            total += 2 * shapes[ins[0]][0] * a["features"]
    return float(total)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed`` up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def make_params(cfg: Mapping, seed: int, device=None):
    """Seeded float32 weights and biases, made on ``device`` in one jitted
    call: weights ``N(0, 1/fan_in)``, biases ``N(0, 0.1**2)``."""
    shapes = param_shapes(cfg)

    def init(key):
        out = {}
        for i, (name, (ws, bs)) in enumerate(sorted(shapes.items())):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = int(np.prod(ws[:-1]))
            out[name] = {
                "w": jax.random.normal(kw, ws, jnp.float32) / np.sqrt(fan_in),
                "b": 0.1 * jax.random.normal(kb, bs, jnp.float32),
            }
        return out

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(init)(key)


def input_pool(cfg: Mapping, size: int, seed: int) -> np.ndarray:
    """``size`` seeded standard-normal float32 inputs, on the host."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.standard_normal((size, *cfg["input_shape"]), dtype=np.float32)


def _split(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``x`` as a bfloat16 high part and a bfloat16 remainder. The rounding
    is ``reduce_precision``, which the compiler keeps; a round trip through
    ``astype`` may be folded away where it allows excess precision."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _product(op, x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """``op(x, w)`` (a conv or a matmul) in float32 at ``precision``."""
    if precision == "highest":
        return op(x, w, jax.lax.Precision.HIGHEST, None)
    if precision == "high_chip":
        return op(x, w, jax.lax.Precision.HIGH, None)
    xh, xl = _split(x)
    wh, wl = _split(w)
    f32 = jnp.float32
    return (op(xh, wh, None, f32) + op(xh, wl, None, f32)) + op(xl, wh, None, f32)


def forward(cfg: Mapping, params, x: jax.Array, precision: str = "highest") -> jax.Array:
    """The configuration's forward pass on a batch ``x`` (NHWC float32)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    vals: Dict[str, jax.Array] = {"input": x}
    last = "input"
    for name, op, ins, a in cfg["layers"]:
        src = [vals[i] for i in ins]
        if op == "conv":
            s = a.get("stride", 1)

            def conv(u, v, prec, pet, s=s):
                return jax.lax.conv_general_dilated(
                    u, v, (s, s), "SAME", dimension_numbers=_DN,
                    precision=prec, preferred_element_type=pet)

            y = _product(conv, src[0], params[name]["w"], precision)
            y = jax.nn.relu(y + params[name]["b"])
        elif op in ("maxpool", "avgpool"):
            k, s = a["kernel"], a["stride"]
            win, strides = (1, k, k, 1), (1, s, s, 1)
            if op == "maxpool":
                y = jax.lax.reduce_window(src[0], -jnp.inf, jax.lax.max, win, strides, "SAME")
            else:
                y = jax.lax.reduce_window(src[0], 0.0, jax.lax.add, win, strides, "SAME")
                y = y / (k * k)
        elif op == "concat":
            y = jnp.concatenate(src, axis=-1)
        elif op == "flatten":
            y = src[0].reshape(src[0].shape[0], -1)
        elif op == "dense":
            def mm(u, v, prec, pet):
                return jnp.matmul(u, v, precision=prec, preferred_element_type=pet)

            y = _product(mm, src[0], params[name]["w"], precision) + params[name]["b"]
            if a.get("relu", True):
                y = jax.nn.relu(y)
        else:
            raise ValueError(f"layer {name}: unknown op {op!r}")
        vals[name] = y
        last = name
    return vals[last]


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, x: forward(cfg, p, x, precision))


def outputs(cfg: Mapping, params, xs: np.ndarray, precision: str = "highest",
            block: int = 16, device=None) -> np.ndarray:
    """Forward pass over the rows of ``xs`` in blocks of ``block`` rows,
    returned on the host."""
    f = _jitted(json.dumps(cfg, sort_keys=True), precision)
    if device is not None:
        params = jax.device_put(params, device)
    outs: List[np.ndarray] = []
    for i in range(0, len(xs), block):
        xb = xs[i:i + block]
        if len(xb) < block:  # one shape per process: pad the last block
            xb = np.concatenate([xb, np.zeros((block - len(xb), *xb.shape[1:]), xb.dtype)])
        xb = jax.device_put(xb, device) if device is not None else xb
        outs.append(np.asarray(f(params, xb)))
    return np.concatenate(outs)[:len(xs)]


def rel_errors(ys: np.ndarray, refs: np.ndarray) -> np.ndarray:
    """Per row: the largest absolute gap to the reference, over the largest
    absolute reference value of that row. Non-finite rows read ``inf``."""
    ys = np.asarray(ys, np.float64).reshape(len(ys), -1)
    refs = np.asarray(refs, np.float64).reshape(len(refs), -1)
    gap = np.abs(ys - refs).max(axis=1)
    scale = np.maximum(np.abs(refs).max(axis=1), np.finfo(np.float32).tiny)
    err = gap / scale
    return np.where(np.isfinite(ys).all(axis=1), err, np.inf)


def compare(ys: np.ndarray, refs: np.ndarray) -> Dict[str, float]:
    """The two numbers a run is judged by, over all its answers.

    ``max_rel_err``, the worst answer's :func:`rel_errors`, catches one
    wrong answer among thousands. ``rms_rel_err``, the root mean square
    over answers of the L2 gap over the L2 norm of the reference, swings
    far less from seed to seed and so separates a lower precision from
    the program's own rounding. An empty or non-finite run reads ``inf``.
    """
    if not len(ys):
        return {"max_rel_err": np.inf, "rms_rel_err": np.inf}
    ys = np.asarray(ys, np.float64).reshape(len(ys), -1)
    refs = np.asarray(refs, np.float64).reshape(len(refs), -1)
    l2 = np.linalg.norm(ys - refs, axis=1) / np.maximum(
        np.linalg.norm(refs, axis=1), np.finfo(np.float32).tiny)
    rms = float(np.sqrt(np.mean(l2 ** 2))) if np.isfinite(ys).all() else np.inf
    return {"max_rel_err": float(rel_errors(ys, refs).max()), "rms_rel_err": rms}


def check_program_matches(cfg: Mapping, layers: Sequence) -> None:
    """Raise unless the program's model has this configuration's layers, at
    the same per-sample output shapes and with the same parameter shapes.
    ``layers`` are the program's layer specs (``name``, ``op``,
    ``out_shape``, ``attrs``)."""
    want = layer_shapes(cfg)
    pshapes = param_shapes(cfg)
    seen = set()
    for spec in layers:
        if spec.name in want:
            seen.add(spec.name)
            if tuple(spec.out_shape) != want[spec.name]:
                raise ValueError(
                    f"layer {spec.name}: program shape {tuple(spec.out_shape)}, "
                    f"configuration {want[spec.name]}")
        if spec.op in ("conv", "dense") and spec.name not in pshapes:
            raise ValueError(f"program layer {spec.name} has weights the "
                             "configuration does not describe")
    missing = set(pshapes) - seen
    if missing:
        raise ValueError(f"configuration layers missing from the program: {sorted(missing)}")
