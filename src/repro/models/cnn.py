"""ACETONE-style layer-DAG CNN models (paper §2.2, §5).

The paper's application model: each network layer is one schedulable task;
the network is an explicit DAG of named layers.  We reproduce the paper's
two evaluation networks:

* **LeNet-5** (Fig. 1) and its *branchified* variant (Fig. 2: the first
  conv/pool stage split into two parallel branches);
* the **GoogLeNet-like** net of Fig. 10 (conv/pool stem + two inception
  modules with 4 parallel branches each + avgpool/gemm head);

and the full **GoogLeNet** it is cut from (Szegedy et al. 2015, Table 1).

Each :class:`LayerSpec` is a pure op over its parents' outputs; layer WCETs
``t(v)`` and edge transfer costs ``w(e)`` come from the roofline cost model,
standing in for the paper's OTAWA bounds (DESIGN §2).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (
    HardwareSpec,
    OpCost,
    TPU_V5E,
    attention_cost,
    box_bytes,
    conv2d_cost,
    conv2d_slice_cost,
    dense_cost,
    elementwise_cost,
    pool2d_cost,
    pool2d_slice_cost,
)
from repro.core.graph import DAG

__all__ = [
    "LayerSpec",
    "CNNModel",
    "lenet5",
    "lenet5_branchy",
    "inception_net",
    "googlenet",
    "transformer_block",
    "apply_layer",
    "run_sequential",
]


# --------------------------------------------------------------------------- #
# SAME-padding tile windows (shared by slice-op semantics and slice costs)
# --------------------------------------------------------------------------- #
def _same_pads(size: int, k: int, s: int) -> Tuple[int, int, int]:
    """XLA/TF ``SAME`` pads for one spatial dim: ``(pad_lo, pad_hi, out)``."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    lo = total // 2
    return lo, total - lo, out


def _row_window(r_lo: int, r_hi: int, size: int, k: int, s: int) -> Tuple[int, int, int, int]:
    """Input-row window (with halo) computing output rows ``[r_lo, r_hi)``.

    Returns ``(a, b, pad_lo, pad_hi)``: read input rows ``[a, b)`` and pad
    them explicitly so a VALID window sweep reproduces exactly the SAME-padded
    layer's output rows ``[r_lo, r_hi)``.
    """
    pt, _pb, _out = _same_pads(size, k, s)
    lo = r_lo * s - pt
    hi = (r_hi - 1) * s + k - pt
    a, b = max(lo, 0), min(hi, size)
    return a, b, a - lo, hi - b


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One ACETONE layer: op + static attributes + parent layer names."""

    name: str
    op: str                      # input|conv|maxpool|avgpool|dense|concat|split|reshape|output
    inputs: Tuple[str, ...]
    out_shape: Tuple[int, ...]   # per-sample (no batch dim)
    attrs: Mapping[str, object] = dataclasses.field(default_factory=dict)

    def cost(self) -> OpCost:
        a = dict(self.attrs)
        if self.op == "conv":
            h, w, cin = a["in_shape"]
            return conv2d_cost(h, w, cin, a["features"], a["kernel"], a["kernel"],
                               stride=a.get("stride", 1))
        if self.op in ("maxpool", "avgpool"):
            h, w, c = a["in_shape"]
            return pool2d_cost(h, w, c, a.get("kernel", 2), stride=a.get("stride", 2))
        if self.op == "dense":
            return dense_cost(a["in_features"], a["features"])
        if self.op == "conv_slice":
            h, w, cin = a["in_shape"]
            k, s = a["kernel"], a.get("stride", 1)
            ra, rb, _plo, _phi = _row_window(a["r_lo"], a["r_hi"], h, k, s)
            _wl, _wr, out_cols = _same_pads(w, k, s)
            return conv2d_slice_cost(
                rb - ra, w, cin, k, k,
                a["r_hi"] - a["r_lo"], out_cols, a["c_hi"] - a["c_lo"],
            )
        if self.op == "pool_slice":
            h, w, _c = a["in_shape"]
            k, s = a.get("kernel", 2), a.get("stride", 2)
            ra, rb, _plo, _phi = _row_window(a["r_lo"], a["r_hi"], h, k, s)
            _wl, _wr, out_cols = _same_pads(w, k, s)
            return pool2d_slice_cost(
                rb - ra, w, a["c_hi"] - a["c_lo"], k,
                a["r_hi"] - a["r_lo"], out_cols,
            )
        if self.op == "dense_slice":
            return dense_cost(a["in_features"], a["f_hi"] - a["f_lo"])
        if self.op in ("attn", "attn_slice"):
            n_heads = (
                a["h_hi"] - a["h_lo"] if self.op == "attn_slice" else a["n_heads"]
            )
            return attention_cost(a["seq"], a["head_dim"], n_heads)
        if self.op == "add":
            return elementwise_cost(int(np.prod(self.out_shape)), flops_per_elem=1.0)
        if self.op in ("concat", "split", "input", "output", "tile_concat"):
            n = int(np.prod(self.out_shape))
            return elementwise_cost(n, flops_per_elem=0.0)
        if self.op == "reshape":
            return OpCost(0.0, 0.0)  # paper Table 1: reshape WCET = 0
        raise ValueError(self.op)

    def out_bytes(self, dtype_bytes: int = 4) -> float:
        return float(np.prod(self.out_shape)) * dtype_bytes


@dataclasses.dataclass(frozen=True)
class CNNModel:
    name: str
    layers: Tuple[LayerSpec, ...]  # topological order

    def spec_map(self) -> Dict[str, LayerSpec]:
        """name -> spec, built once (executors look specs up per node per
        superstep; sliced models have hundreds of layers, so the linear scan
        this replaces was O(L^2) across a plan)."""
        cache = self.__dict__.get("_spec_map")
        if cache is None:
            cache = {l.name: l for l in self.layers}
            object.__setattr__(self, "_spec_map", cache)
        return cache

    def spec(self, name: str) -> LayerSpec:
        return self.spec_map()[name]

    # -------------------------------------------------------------- #
    def init_params(self, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
        params: Dict[str, Dict[str, jax.Array]] = {}
        for l in self.layers:
            # crc32, not hash(): str hashes change with PYTHONHASHSEED, and
            # a run and its reference in two processes must see one model
            k = jax.random.fold_in(key, zlib.crc32(l.name.encode()) % (2**31))
            if l.op == "conv":
                a = l.attrs
                cin = a["in_shape"][2]
                wshape = (a["kernel"], a["kernel"], cin, a["features"])
                params[l.name] = {
                    "w": jax.random.normal(k, wshape, jnp.float32)
                    / np.sqrt(a["kernel"] * a["kernel"] * cin),
                    "b": jnp.zeros((a["features"],), jnp.float32),
                }
            elif l.op == "dense":
                a = l.attrs
                wshape = (a["in_features"], a["features"])
                params[l.name] = {
                    "w": jax.random.normal(k, wshape, jnp.float32)
                    / np.sqrt(a["in_features"]),
                    "b": jnp.zeros((a["features"],), jnp.float32),
                }
        return params

    # -------------------------------------------------------------- #
    def to_dag(self, hw: HardwareSpec = TPU_V5E, time_unit: float = 1e-9) -> DAG:
        """Cost-annotated task DAG (t in ``time_unit`` seconds).

        Edge weights default to the *producer's* output bytes, so slice-task
        edges are priced at actual tile bytes; direct slice-to-slice edges
        carry ``attrs["in_boxes"]`` — the consumer-window ∩ producer-tile
        intersection — and are priced at exactly those bytes.  Boxes are
        per-axis interval tuples, so 1-D tiles and 2-D (cout × rows) grid
        tiles price identically.  Node metadata records each task's op,
        originating layer, tile coordinates and input boxes (``in_boxes``,
        parent-edge aligned), which ``build_plan`` uses to ship windowed
        transfer payloads.
        """
        t = {l.name: max(l.cost().time(hw) / time_unit, 1e-3) for l in self.layers}
        edges = []
        w = {}
        meta = {}
        for l in self.layers:
            m = {"op": l.op, "origin": l.attrs.get("origin", l.name)}
            if "tile" in l.attrs:
                m["tile"] = l.attrs["tile"]
            in_boxes = l.attrs.get("in_boxes")
            # a layer may read the same producer through several slots (a
            # residual add of one tensor, glue concatenating two windows of
            # one tile): the DAG carries one edge per distinct parent, so
            # duplicate slots collapse — their windows union (``None`` = a
            # whole-register read wins), and the edge is priced at the union
            ded: List[str] = []
            ded_idx: Dict[str, int] = {}
            ded_boxes: List[Optional[Tuple[Tuple[int, int], ...]]] = []
            for idx, p in enumerate(self.inputs_of(l.name)):
                box = in_boxes[idx] if in_boxes is not None else None
                if p in ded_idx:
                    j = ded_idx[p]
                    old = ded_boxes[j]
                    ded_boxes[j] = None if (old is None or box is None) else tuple(
                        (min(a, lo), max(b, hi))
                        for (a, b), (lo, hi) in zip(old, box)
                    )
                else:
                    ded_idx[p] = len(ded)
                    ded.append(p)
                    ded_boxes.append(box)
            if in_boxes is not None:
                m["in_boxes"] = tuple(ded_boxes)
            meta[l.name] = m
            for p, box in zip(ded, ded_boxes):
                e = (p, l.name)
                edges.append(e)
                b = box_bytes(box) if box is not None else self.spec(p).out_bytes()
                w[e] = hw.comm_time(b) / time_unit
        return DAG.build(
            nodes=tuple(l.name for l in self.layers), edges=tuple(edges), t=t, w=w,
            meta=meta,
        )

    def inputs_of(self, name: str) -> Tuple[str, ...]:
        return self.spec(name).inputs


# --------------------------------------------------------------------------- #
# op semantics (batched NHWC)
# --------------------------------------------------------------------------- #
def _assemble_inputs(
    layout, boxes, inputs: Sequence[jax.Array]
) -> Tuple[List[jax.Array], List[Tuple[int, int]]]:
    """Reassemble logical inputs from direct tile edges (nested tiling IR).

    ``layout`` (``attrs["in_layout"]``, from the slicer) maps each logical
    slot to either ``None`` — one input tensor, passed through whole — or
    ``(base, tree)``: ``tree`` is a nested assembly whose leaves (``None``)
    consume the next input tensor cropped to its ``boxes`` window
    (tile-local; ``None`` = the whole tile) and whose internal nodes
    ``(axis, children)`` concatenate child blocks along per-sample
    ``axis``.  Cropping every leaf makes the assembled block exactly the
    consumer's input window, whose per-axis low corner is ``base`` — rows
    of channel blocks for 2-D grids assemble the same way as 1-D tilings.
    Returns the logical tensors plus per-slot ``(row, last-axis)`` offsets
    so ops can shift their static windows into block coordinates.
    """
    vals: List[jax.Array] = []
    offs: List[Tuple[int, int]] = []
    i = 0

    def build(tree) -> jax.Array:
        nonlocal i
        if tree is None:  # leaf: one producer tile, cropped to its window
            x = inputs[i]
            crop = boxes[i]
            i += 1
            if crop is not None:
                x = x[(slice(None), *(slice(lo, hi) for (lo, hi) in crop))]
            return x
        axis, kids = tree
        parts = [build(k) for k in kids]
        bax = axis + 1 if axis >= 0 else axis  # per-sample -> batched axis
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=bax)

    for ent in layout:
        if ent is None:
            vals.append(inputs[i])
            offs.append((0, 0))
            i += 1
            continue
        base, tree = ent
        vals.append(build(tree))
        offs.append((base[0] if len(base) > 1 else 0, base[-1]))
    return vals, offs


def _slot_offsets(offs, slot: int) -> Tuple[int, int]:
    """(row offset, last-axis offset) of logical input ``slot``."""
    return offs[slot]


def apply_layer(
    spec: LayerSpec,
    params: Mapping[str, Mapping[str, jax.Array]],
    inputs: Sequence[jax.Array],
) -> jax.Array:
    a = dict(spec.attrs)
    if "in_layout" in a:
        boxes = a.get("in_boxes", (None,) * len(inputs))
        inputs, offs = _assemble_inputs(a["in_layout"], boxes, inputs)
    else:
        offs = [(0, 0)] * len(inputs)
    if spec.op == "input":
        (x,) = inputs
        return x
    if spec.op == "conv":
        (x,) = inputs
        s = a.get("stride", 1)
        y = jax.lax.conv_general_dilated(
            x, params[spec.name]["w"], (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + params[spec.name]["b"]
        return jax.nn.relu(y)
    if spec.op in ("maxpool", "avgpool"):
        (x,) = inputs
        k = a.get("kernel", 2)
        s = a.get("stride", 2)
        if spec.op == "maxpool":
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1), "SAME"
            )
        y = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, k, k, 1), (1, s, s, 1), "SAME"
        )
        return y / (k * k)
    if spec.op == "dense":
        (x,) = inputs
        y = x @ params[spec.name]["w"] + params[spec.name]["b"]
        return jax.nn.relu(y) if a.get("relu", True) else y
    if spec.op == "conv_slice":
        # one tile of a conv layer: output rows [r_lo, r_hi) x output
        # channels [c_lo, c_hi), reading the halo'd input row window and the
        # originating layer's weight slice (bit-exact vs. conv + slicing).
        # Under direct tile edges the input block may start at a row offset
        # (subset of a row-tiled producer); the static window shifts with it.
        (x,) = inputs
        r_off, _ = _slot_offsets(offs, 0)
        h, w, _cin = a["in_shape"]
        k, s = a["kernel"], a.get("stride", 1)
        ra, rb, plo, phi = _row_window(a["r_lo"], a["r_hi"], h, k, s)
        wl, wr, _ = _same_pads(w, k, s)
        p = params[a["origin"]]
        y = jax.lax.conv_general_dilated(
            x[:, ra - r_off:rb - r_off], p["w"][..., a["c_lo"]:a["c_hi"]], (s, s),
            [(plo, phi), (wl, wr)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["b"][a["c_lo"]:a["c_hi"]]
        return jax.nn.relu(y)
    if spec.op == "pool_slice":
        (x,) = inputs
        r_off, c_off = _slot_offsets(offs, 0)
        h, w, _c = a["in_shape"]
        k, s = a.get("kernel", 2), a.get("stride", 2)
        ra, rb, plo, phi = _row_window(a["r_lo"], a["r_hi"], h, k, s)
        wl, wr, _ = _same_pads(w, k, s)
        xs = x[:, ra - r_off:rb - r_off, :, a["c_lo"] - c_off:a["c_hi"] - c_off]
        pads = ((0, 0), (plo, phi), (wl, wr), (0, 0))
        if a["pool"] == "maxpool":
            return jax.lax.reduce_window(
                xs, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1), pads
            )
        y = jax.lax.reduce_window(
            xs, 0.0, jax.lax.add, (1, k, k, 1), (1, s, s, 1), pads
        )
        return y / (k * k)
    if spec.op == "dense_slice":
        (x,) = inputs
        p = params[a["origin"]]
        y = x @ p["w"][:, a["f_lo"]:a["f_hi"]] + p["b"][a["f_lo"]:a["f_hi"]]
        return jax.nn.relu(y) if a.get("relu", True) else y
    if spec.op in ("attn", "attn_slice"):
        q, k, v = inputs
        hd, n_heads = a["head_dim"], a["n_heads"]
        h_lo, h_hi = (
            (a["h_lo"], a["h_hi"]) if spec.op == "attn_slice" else (0, n_heads)
        )
        b_, s_ = q.shape[0], q.shape[1]

        def heads(t: jax.Array, slot: int) -> jax.Array:
            # a head block is a contiguous feature column range; with direct
            # tile edges the projection arrives as a sub-block starting at a
            # feature offset, so window first, then fold into heads
            _, f_off = _slot_offsets(offs, slot)
            cols = t[..., h_lo * hd - f_off:h_hi * hd - f_off]
            return cols.reshape(b_, s_, h_hi - h_lo, hd)

        scores = jnp.einsum("bqhd,bkhd->bhqk", heads(q, 0), heads(k, 1)) / np.sqrt(hd)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, heads(v, 2))
        return o.reshape(b_, s_, (h_hi - h_lo) * hd)
    if spec.op == "add":
        x1, x2 = inputs
        return x1 + x2
    if spec.op == "tile_concat":
        # glue always carries in_layout (built by the slicer's _glue_spec),
        # so the nested reassembly already ran above
        (x,) = inputs
        return x
    if spec.op == "concat":
        return jnp.concatenate(list(inputs), axis=-1)
    if spec.op == "split":
        (x,) = inputs
        lo, hi = a["channels"]
        return x[..., lo:hi]
    if spec.op == "reshape":
        (x,) = inputs
        return x.reshape(x.shape[0], -1)
    if spec.op == "output":
        (x,) = inputs
        return x
    raise ValueError(spec.op)


def run_sequential(
    model: CNNModel,
    params: Mapping[str, Mapping[str, jax.Array]],
    x: jax.Array,
) -> jax.Array:
    """Reference execution in topological order (ACETONE's sequential code)."""
    vals: Dict[str, jax.Array] = {}
    for l in model.layers:
        ins = [x] if l.op == "input" else [vals[p] for p in l.inputs]
        vals[l.name] = apply_layer(l, params, ins)
    return vals[model.layers[-1].name]


# --------------------------------------------------------------------------- #
# model builders
# --------------------------------------------------------------------------- #
def _conv(name, parent, in_shape, features, kernel, stride=1) -> LayerSpec:
    h, w, _ = in_shape
    out = (h // stride, w // stride, features)
    return LayerSpec(name, "conv", (parent,), out,
                     {"in_shape": in_shape, "features": features,
                      "kernel": kernel, "stride": stride})


def _pool(name, op, parent, in_shape, kernel=2, stride=2) -> LayerSpec:
    h, w, c = in_shape
    out = ((h + stride - 1) // stride, (w + stride - 1) // stride, c)
    return LayerSpec(name, op, (parent,), out,
                     {"in_shape": in_shape, "kernel": kernel, "stride": stride})


def _dense(name, parent, n_in, n_out, relu=True) -> LayerSpec:
    return LayerSpec(name, "dense", (parent,), (n_out,),
                     {"in_features": n_in, "features": n_out, "relu": relu})


def lenet5(input_hw: int = 28) -> CNNModel:
    """Sequential LeNet-5 (paper Fig. 1)."""
    s = input_hw
    ls: List[LayerSpec] = [LayerSpec("input", "input", (), (s, s, 1))]
    ls.append(_conv("conv1", "input", (s, s, 1), 6, 5))
    ls.append(_pool("pool1", "maxpool", "conv1", (s, s, 6)))
    s2 = s // 2
    ls.append(_conv("conv2", "pool1", (s2, s2, 6), 16, 5))
    ls.append(_pool("pool2", "maxpool", "conv2", (s2, s2, 16)))
    s4 = s2 // 2
    flat = s4 * s4 * 16
    ls.append(LayerSpec("flatten", "reshape", ("pool2",), (flat,)))
    ls.append(_dense("dense1", "flatten", flat, 120))
    ls.append(_dense("dense2", "dense1", 120, 84))
    ls.append(_dense("dense3", "dense2", 84, 10, relu=False))
    ls.append(LayerSpec("output", "output", ("dense3",), (10,)))
    return CNNModel("lenet5", tuple(ls))


def lenet5_branchy(input_hw: int = 28) -> CNNModel:
    """Branchified LeNet-5 (paper Fig. 2): first conv/pool stage split in two."""
    s = input_hw
    ls: List[LayerSpec] = [LayerSpec("input", "input", (), (s, s, 1))]
    # the split duplicates the single input channel to both branches
    ls.append(LayerSpec("split_top", "split", ("input",), (s, s, 1), {"channels": (0, 1)}))
    ls.append(LayerSpec("split_bot", "split", ("input",), (s, s, 1), {"channels": (0, 1)}))
    ls.append(_conv("conv1_top", "split_top", (s, s, 1), 3, 5))
    ls.append(_conv("conv1_bot", "split_bot", (s, s, 1), 3, 5))
    ls.append(_pool("pool1_top", "maxpool", "conv1_top", (s, s, 3)))
    ls.append(_pool("pool1_bot", "maxpool", "conv1_bot", (s, s, 3)))
    s2 = s // 2
    ls.append(LayerSpec("concat", "concat", ("pool1_top", "pool1_bot"), (s2, s2, 6)))
    ls.append(_conv("conv2", "concat", (s2, s2, 6), 16, 5))
    ls.append(_pool("pool2", "maxpool", "conv2", (s2, s2, 16)))
    s4 = s2 // 2
    flat = s4 * s4 * 16
    ls.append(LayerSpec("flatten", "reshape", ("pool2",), (flat,)))
    ls.append(_dense("dense1", "flatten", flat, 120))
    ls.append(_dense("dense2", "dense1", 120, 84))
    ls.append(_dense("dense3", "dense2", 84, 10, relu=False))
    ls.append(LayerSpec("output", "output", ("dense3",), (10,)))
    return CNNModel("lenet5_branchy", tuple(ls))


def _inception(ls: List[LayerSpec], tag: str, parent: str, in_shape,
               f_a: int, f_b1: int, f_b2: int, f_c1: int, f_c2: int, f_d: int):
    """GoogLeNet inception module (paper Fig. 10 right box): 4 branches."""
    h, w, _ = in_shape
    ls.append(_conv(f"{tag}/conv_a", parent, in_shape, f_a, 1))
    ls.append(_conv(f"{tag}/conv_b1", parent, in_shape, f_b1, 1))
    ls.append(_conv(f"{tag}/conv_b2", f"{tag}/conv_b1", (h, w, f_b1), f_b2, 3))
    ls.append(_conv(f"{tag}/conv_c1", parent, in_shape, f_c1, 1))
    ls.append(_conv(f"{tag}/conv_c2", f"{tag}/conv_c1", (h, w, f_c1), f_c2, 5))
    ls.append(_pool(f"{tag}/maxpool", "maxpool", parent, in_shape, kernel=3, stride=1))
    ls.append(_conv(f"{tag}/conv_d", f"{tag}/maxpool", in_shape, f_d, 1))
    cout = f_a + f_b2 + f_c2 + f_d
    ls.append(LayerSpec(
        f"{tag}/concat", "concat",
        (f"{tag}/conv_a", f"{tag}/conv_b2", f"{tag}/conv_c2", f"{tag}/conv_d"),
        (h, w, cout),
    ))
    return (h, w, cout)


def inception_net(input_hw: int = 224, n_classes: int = 10) -> CNNModel:
    """The GoogLeNet-like network of paper Fig. 10 / Tables 1-3."""
    s = input_hw
    ls: List[LayerSpec] = [LayerSpec("input", "input", (), (s, s, 3))]
    ls.append(_conv("conv_1", "input", (s, s, 3), 64, 7, stride=2))
    s = s // 2
    ls.append(_pool("maxpool_1", "maxpool", "conv_1", (s, s, 64), kernel=3, stride=2))
    s = (s + 1) // 2
    ls.append(_conv("conv_2", "maxpool_1", (s, s, 64), 192, 3))
    ls.append(_pool("maxpool_2", "maxpool", "conv_2", (s, s, 192), kernel=3, stride=2))
    s = (s + 1) // 2
    shape = _inception(ls, "inception_1", "maxpool_2", (s, s, 192),
                       64, 96, 128, 16, 32, 32)
    shape = _inception(ls, "inception_2", f"inception_1/concat", shape,
                       128, 128, 192, 32, 96, 64)
    h, w, c = shape
    ls.append(_pool("avgpool", "avgpool", "inception_2/concat", shape,
                    kernel=h, stride=h))
    ls.append(LayerSpec("reshape", "reshape", ("avgpool",), (c,)))
    ls.append(_dense("gemm", "reshape", c, n_classes, relu=False))
    ls.append(LayerSpec("output", "output", ("gemm",), (n_classes,)))
    return CNNModel("inception", tuple(ls))


# Table 1 of arXiv:1409.4842, per inception module: #1x1, #3x3 reduce, #3x3,
# #5x5 reduce, #5x5, pool proj; a stride-2 max pool follows stages 3 and 4
GOOGLENET_STAGES: Tuple[Tuple[Tuple[str, Tuple[int, ...]], ...], ...] = (
    (("3a", (64, 96, 128, 16, 32, 32)),
     ("3b", (128, 128, 192, 32, 96, 64))),
    (("4a", (192, 96, 208, 16, 48, 64)),
     ("4b", (160, 112, 224, 24, 64, 64)),
     ("4c", (128, 128, 256, 24, 64, 64)),
     ("4d", (112, 144, 288, 32, 64, 64)),
     ("4e", (256, 160, 320, 32, 128, 128))),
    (("5a", (256, 160, 320, 32, 128, 128)),
     ("5b", (384, 192, 384, 48, 128, 128))),
)


def googlenet(input_hw: int = 224, n_classes: int = 1000) -> CNNModel:
    """GoogLeNet (Szegedy et al. 2015, arXiv:1409.4842, Table 1), the
    inference path at its published widths: the 7x7/2 stem, the 1x1
    ``conv_2_reduce`` and 3x3 ``conv_2``, nine inception modules in three
    stages with a 3x3/2 max pool after the stem and after stages 3 and 4,
    a global average pool and a dense head with no ReLU.

    Departures from the paper: no local response normalisation (as in
    torchvision's GoogLeNet); no softmax, so the head returns logits;
    dropout is the identity at inference; the two auxiliary classifiers
    are training-only and not built. Every conv and pool pads SAME, as
    TensorFlow's Inception v1 does. For the 3x3/2 pools at 112, 56, 28 and
    14 that reads the same windows as Caffe's ceil-mode pools; Caffe's
    7x7/2 conv pads 3 rows and columns before, SAME pads 2 before and 3
    after.
    """
    s = input_hw
    ls: List[LayerSpec] = [LayerSpec("input", "input", (), (s, s, 3))]
    ls.append(_conv("conv_1", "input", (s, s, 3), 64, 7, stride=2))
    s = s // 2
    ls.append(_pool("maxpool_1", "maxpool", "conv_1", (s, s, 64), kernel=3, stride=2))
    s = (s + 1) // 2
    ls.append(_conv("conv_2_reduce", "maxpool_1", (s, s, 64), 64, 1))
    ls.append(_conv("conv_2", "conv_2_reduce", (s, s, 64), 192, 3))
    ls.append(_pool("maxpool_2", "maxpool", "conv_2", (s, s, 192), kernel=3, stride=2))
    parent, shape = "maxpool_2", ((s + 1) // 2, (s + 1) // 2, 192)
    for stage, modules in enumerate(GOOGLENET_STAGES, start=3):
        if stage > 3:
            name = f"maxpool_{stage - 1}"
            ls.append(_pool(name, "maxpool", parent, shape, kernel=3, stride=2))
            parent, shape = name, ls[-1].out_shape
        for tag, widths in modules:
            shape = _inception(ls, f"inception_{tag}", parent, shape, *widths)
            parent = f"inception_{tag}/concat"
    h, w, c = shape
    ls.append(_pool("avgpool", "avgpool", parent, shape, kernel=h, stride=h))
    ls.append(LayerSpec("flatten", "reshape", ("avgpool",), (c,)))
    ls.append(_dense("fc", "flatten", c, n_classes, relu=False))
    ls.append(LayerSpec("output", "output", ("fc",), (n_classes,)))
    return CNNModel("googlenet", tuple(ls))


def transformer_block(
    seq: int = 64, d_model: int = 128, n_heads: int = 8, d_ff: int = 256
) -> CNNModel:
    """One pre-LN-free transformer block as an explicit layer DAG.

    QKV projections, multi-head attention, output projection and a 2-layer
    FFN with residual adds — the layer-granularity view the slicer lowers to
    head blocks (attention) and row blocks (dense).  Activations are
    ``(seq, d)`` per sample, so the CNN scheduling/codegen pipeline applies
    unchanged.
    """
    if d_model % n_heads:
        raise ValueError("d_model must divide into heads")
    hd = d_model // n_heads
    dm = (seq, d_model)
    proj = {"in_features": d_model, "features": d_model, "relu": False}
    ls: List[LayerSpec] = [LayerSpec("input", "input", (), dm)]
    ls.append(LayerSpec("wq", "dense", ("input",), dm, dict(proj)))
    ls.append(LayerSpec("wk", "dense", ("input",), dm, dict(proj)))
    ls.append(LayerSpec("wv", "dense", ("input",), dm, dict(proj)))
    ls.append(LayerSpec("attn", "attn", ("wq", "wk", "wv"), dm,
                        {"n_heads": n_heads, "head_dim": hd, "seq": seq}))
    ls.append(LayerSpec("wo", "dense", ("attn",), dm, dict(proj)))
    ls.append(LayerSpec("res1", "add", ("input", "wo"), dm))
    ls.append(LayerSpec("ffn1", "dense", ("res1",), (seq, d_ff),
                        {"in_features": d_model, "features": d_ff, "relu": True}))
    ls.append(LayerSpec("ffn2", "dense", ("ffn1",), dm,
                        {"in_features": d_ff, "features": d_model, "relu": False}))
    ls.append(LayerSpec("res2", "add", ("res1", "ffn2"), dm))
    ls.append(LayerSpec("output", "output", ("res2",), dm))
    return CNNModel("transformer_block", tuple(ls))
