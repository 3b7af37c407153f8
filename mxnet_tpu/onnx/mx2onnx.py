"""mx2onnx — export a Symbol graph + params to ONNX.

Reference surface: ``python/mxnet/onnx/mx2onnx`` (SURVEY.md §3.2 "ONNX":
"op-by-op converter registry").  Each registered converter maps ONE graph
node (op name + attrs) to one-or-more ONNX node descriptors.

The ``onnx`` package is not installed in this environment; the converter
registry and graph construction are fully functional, and serialization
picks the best available container:

- with ``onnx`` importable → a real ``ModelProto`` written to ``.onnx``
- otherwise → the same graph as deterministic JSON (``.onnx.json``),
  loadable by the companion importer and by the tests.
"""
from __future__ import annotations

import json
import warnings

import numpy as onp

from ..base import MXNetError

_CONVERTERS = {}

_OPSET = 13


def register_converter(opname):
    def deco(fn):
        _CONVERTERS[opname] = fn
        return fn
    return deco


def get_converter_registry():
    return dict(_CONVERTERS)


def _node(op_type, inputs, outputs, name, **attrs):
    return {"op_type": op_type, "inputs": list(inputs),
            "outputs": list(outputs), "name": name, "attrs": attrs}


# --------------------------------------------------------------------- #
# converters: fn(node_name, input_names, output_name, attrs) -> [nodes]
# --------------------------------------------------------------------- #

@register_converter("FullyConnected")
def _conv_fc(name, ins, out, attrs):
    nodes = []
    data = ins[0]
    if attrs.get("flatten", True):
        nodes.append(_node("Flatten", [data], [f"{name}_flat"],
                           f"{name}_flatten", axis=1))
        data = f"{name}_flat"
    gemm_ins = [data, ins[1]] + (ins[2:3] if len(ins) > 2 else [])
    nodes.append(_node("Gemm", gemm_ins, [out], name, alpha=1.0, beta=1.0,
                       transA=0, transB=1))
    return nodes


@register_converter("Convolution")
def _conv_conv(name, ins, out, attrs):
    kernel = list(attrs.get("kernel", ()))
    return [_node("Conv", ins, [out], name,
                  kernel_shape=kernel,
                  strides=list(attrs.get("stride", ())) or [1] * len(kernel),
                  pads=list(attrs.get("pad", ())) * 2 or [0] * 2 * len(kernel),
                  dilations=list(attrs.get("dilate", ())) or [1] * len(kernel),
                  group=int(attrs.get("num_group", 1)))]


@register_converter("Activation")
def _conv_act(name, ins, out, attrs):
    table = {"relu": "Relu", "sigmoid": "Sigmoid", "tanh": "Tanh",
             "softrelu": "Softplus", "softsign": "Softsign"}
    act = attrs.get("act_type", "relu")
    if act == "erf_gelu":
        # exact-erf gelu: 0.5·x·(1 + erf(x/√2)) — ONNX has no Gelu until
        # opset 20
        x = ins[0]
        return [
            _node("Div", [x, f"{name}_sqrt2"], [f"{name}_xs"], f"{name}_d",
                  _const={f"{name}_sqrt2":
                          onp.asarray(2.0 ** 0.5, onp.float32)}),
            _node("Erf", [f"{name}_xs"], [f"{name}_erf"], f"{name}_e"),
            _node("Add", [f"{name}_erf", f"{name}_one"], [f"{name}_1p"],
                  f"{name}_a",
                  _const={f"{name}_one": onp.asarray(1.0, onp.float32)}),
            _node("Mul", [x, f"{name}_1p"], [f"{name}_x1p"], f"{name}_m"),
            _node("Mul", [f"{name}_x1p", f"{name}_half"], [out], name,
                  _const={f"{name}_half":
                          onp.asarray(0.5, onp.float32)}),
        ]
    if act == "gelu":
        # the runtime's Activation('gelu') is jax.nn.gelu's TANH
        # approximation (ops/nn.py) — export the matching decomposition:
        # 0.5·x·(1 + tanh(√(2/π)·(x + 0.044715·x³)))
        x = ins[0]
        return [
            _node("Mul", [x, x], [f"{name}_x2"], f"{name}_sq"),
            _node("Mul", [f"{name}_x2", x], [f"{name}_x3"], f"{name}_cu"),
            _node("Mul", [f"{name}_x3", f"{name}_c0"], [f"{name}_cx3"],
                  f"{name}_m0",
                  _const={f"{name}_c0":
                          onp.asarray(0.044715, onp.float32)}),
            _node("Add", [x, f"{name}_cx3"], [f"{name}_in"], f"{name}_a0"),
            _node("Mul", [f"{name}_in", f"{name}_c1"], [f"{name}_sc"],
                  f"{name}_m1",
                  _const={f"{name}_c1":
                          onp.asarray((2.0 / onp.pi) ** 0.5,
                                      onp.float32)}),
            _node("Tanh", [f"{name}_sc"], [f"{name}_t"], f"{name}_th"),
            _node("Add", [f"{name}_t", f"{name}_one"], [f"{name}_1p"],
                  f"{name}_a1",
                  _const={f"{name}_one": onp.asarray(1.0, onp.float32)}),
            _node("Mul", [x, f"{name}_1p"], [f"{name}_x1p"], f"{name}_m2"),
            _node("Mul", [f"{name}_x1p", f"{name}_half"], [out], name,
                  _const={f"{name}_half":
                          onp.asarray(0.5, onp.float32)}),
        ]
    if act not in table:
        raise MXNetError(f"onnx: unsupported activation {act}")
    return [_node(table[act], ins, [out], name)]


@register_converter("relu")
def _conv_relu(name, ins, out, attrs):
    return [_node("Relu", ins, [out], name)]


@register_converter("sigmoid")
def _conv_sigmoid(name, ins, out, attrs):
    return [_node("Sigmoid", ins, [out], name)]


@register_converter("tanh")
def _conv_tanh(name, ins, out, attrs):
    return [_node("Tanh", ins, [out], name)]


@register_converter("softmax")
def _conv_softmax(name, ins, out, attrs):
    return [_node("Softmax", ins, [out], name,
                  axis=int(attrs.get("axis", -1)))]


@register_converter("log_softmax")
def _conv_log_softmax(name, ins, out, attrs):
    return [_node("LogSoftmax", ins, [out], name,
                  axis=int(attrs.get("axis", -1)))]


@register_converter("_BatchNormStats")
def _conv_bn(name, ins, out, attrs):
    # inputs: data, gamma, beta, moving_mean, moving_var (inference form)
    return [_node("BatchNormalization", ins[:5], [out], name,
                  epsilon=float(attrs.get("eps", 1e-5)),
                  momentum=float(attrs.get("momentum", 0.9)))]


@register_converter("LayerNorm")
def _conv_ln(name, ins, out, attrs):
    return [_node("LayerNormalization", ins, [out], name,
                  axis=int(attrs.get("axis", -1)),
                  epsilon=float(attrs.get("eps", 1e-5)))]


@register_converter("Pooling")
def _conv_pool(name, ins, out, attrs):
    ptype = attrs.get("pool_type", "max")
    if attrs.get("global_pool", False):
        op_type = "GlobalMaxPool" if ptype == "max" else "GlobalAveragePool"
        return [_node(op_type, ins, [out], name)]
    kernel = list(attrs.get("kernel", ()))
    op_type = "MaxPool" if ptype == "max" else "AveragePool"
    return [_node(op_type, ins, [out], name, kernel_shape=kernel,
                  strides=list(attrs.get("stride", ())) or [1] * len(kernel),
                  pads=list(attrs.get("pad", ())) * 2 or [0] * 2 * len(kernel))]


@register_converter("flatten")
def _conv_flatten(name, ins, out, attrs):
    return [_node("Flatten", ins, [out], name, axis=1)]


@register_converter("reshape")
def _conv_reshape(name, ins, out, attrs):
    return [_node("Reshape", ins + [f"{name}_shape"], [out], name,
                  _const={f"{name}_shape":
                          onp.asarray(attrs.get("shape", (-1,)),
                                      onp.int64)})]


@register_converter("transpose")
def _conv_transpose(name, ins, out, attrs):
    return [_node("Transpose", ins, [out], name,
                  perm=list(attrs.get("axes", ())))]


@register_converter("concat")
def _conv_concat(name, ins, out, attrs):
    return [_node("Concat", ins, [out], name,
                  axis=int(attrs.get("dim", 1)))]


@register_converter("Embedding")
def _conv_embedding(name, ins, out, attrs):
    # data, weight -> Gather(weight, data)
    return [_node("Gather", [ins[1], ins[0]], [out], name, axis=0)]


@register_converter("dot")
def _conv_dot(name, ins, out, attrs):
    a, b = ins
    nodes = []
    # MXNet dot carries transpose flags; ONNX MatMul does not (2-D only —
    # batched dot exports via the batch_dot/matmul path).  dot on rank>2
    # is tensordot, which MatMul does NOT express — refuse loudly rather
    # than exporting silently wrong batched semantics.
    in_shapes = attrs.get("_in_shapes")
    if not in_shapes:
        # Without shape info the plain no-transpose dot exports as MatMul
        # — identical semantics for the 2-D case, which is what a
        # shape-free graph's dot overwhelmingly is, and what the
        # reference exporter emits.  It is NOT identical for rank>2
        # operands (dot is tensordot over the last/first axes; ONNX
        # MatMul batches), so the assumption is surfaced as a warning
        # rather than made silently.  The transpose flags lower to a
        # rank-2 Transpose(perm=[1,0]) and would be structurally wrong
        # without rank proof, so those still demand shapes.
        if attrs.get("transpose_a") or attrs.get("transpose_b"):
            raise MXNetError(
                "onnx: dot with transpose_a/transpose_b needs "
                "input_shapes at export time to prove the operands are "
                "2-D (the flags lower to a rank-2 Transpose)")
        warnings.warn(
            f"onnx: exporting shape-free dot '{name}' as MatMul, which "
            "assumes 2-D operands; rank>2 dot is tensordot and would "
            "need input_shapes at export time to refuse correctly",
            stacklevel=2)
        return [_node("MatMul", [a, b], [out], name)]
    if any(len(s) != 2 for s in in_shapes[:2]):
        raise MXNetError(
            f"onnx: dot export supports 2-D operands only, got shapes "
            f"{in_shapes[:2]} (rank>2 dot is tensordot — restructure "
            "with batch_dot/matmul)")
    if attrs.get("transpose_a"):
        nodes.append(_node("Transpose", [a], [f"{name}_aT"], f"{name}_ta",
                           perm=[1, 0]))
        a = f"{name}_aT"
    if attrs.get("transpose_b"):
        nodes.append(_node("Transpose", [b], [f"{name}_bT"], f"{name}_tb",
                           perm=[1, 0]))
        b = f"{name}_bT"
    nodes.append(_node("MatMul", [a, b], [out], name))
    return nodes


@register_converter("matmul")
def _conv_matmul(name, ins, out, attrs):
    return [_node("MatMul", ins, [out], name)]


@register_converter("slice_axis")
def _conv_slice_axis(name, ins, out, attrs):
    axis = int(attrs.get("axis", 0))
    begin = int(attrs.get("begin", 0))
    end = attrs.get("end")
    end = onp.iinfo(onp.int64).max if end is None else int(end)
    return [_node("Slice",
                  ins + [f"{name}_starts", f"{name}_ends", f"{name}_axes"],
                  [out], name,
                  _const={f"{name}_starts": onp.asarray([begin], onp.int64),
                          f"{name}_ends": onp.asarray([end], onp.int64),
                          f"{name}_axes": onp.asarray([axis], onp.int64)})]


@register_converter("broadcast_to")
def _conv_broadcast_to(name, ins, out, attrs):
    shape = list(attrs.get("shape", ()))
    if any(int(d) == 0 for d in shape):
        # MXNet's '0 keeps the input dim' has no ONNX Expand equivalent —
        # resolve against the inferred input shape
        in_shp = (attrs.get("_in_shapes") or [None])[0]
        if in_shp is None or len(in_shp) != len(shape):
            raise MXNetError(
                "onnx: broadcast_to with 0-dims ('keep input dim') needs "
                "input_shapes at export time to resolve them")
        shape = [int(i) if int(d) == 0 else int(d)
                 for d, i in zip(shape, in_shp)]
    return [_node("Expand", ins + [f"{name}_shape"], [out], name,
                  _const={f"{name}_shape": onp.asarray(shape, onp.int64)})]


@register_converter("flash_attention")
def _conv_flash(name, ins, out, attrs):
    """Decompose the fused attention op into the canonical ONNX pattern:
    MatMul(q, kᵀ)·scale [+ bias] → Softmax → MatMul(·, v).  The fused
    kernel is a TPU-side optimization; exported models get the portable
    graph every runtime understands."""
    if attrs.get("causal"):
        raise MXNetError(
            "onnx: causal flash_attention export not supported yet — "
            "encoder (BERT-style) attention only")
    scale = attrs.get("scale")
    if scale is None:
        shp = (attrs.get("_in_shapes") or [None])[0]
        if not shp:
            raise MXNetError(
                "onnx: flash_attention export needs input_shapes (to "
                "derive scale = 1/sqrt(head_dim)) or an explicit scale")
        scale = 1.0 / (float(shp[-1]) ** 0.5)
    q, k, v = ins[:3]
    bias = ins[3] if len(ins) > 3 else None
    nodes = [
        _node("Transpose", [k], [f"{name}_kT"], f"{name}_kt",
              perm=[0, 1, 3, 2]),
        _node("MatMul", [q, f"{name}_kT"], [f"{name}_qk"], f"{name}_qkm"),
        _node("Mul", [f"{name}_qk", f"{name}_scale"], [f"{name}_s"],
              f"{name}_sc",
              _const={f"{name}_scale": onp.asarray(scale, onp.float32)}),
    ]
    scores = f"{name}_s"
    if bias is not None:
        nodes.append(_node("Add", [scores, bias], [f"{name}_sb"],
                           f"{name}_ab"))
        scores = f"{name}_sb"
    nodes += [
        _node("Softmax", [scores], [f"{name}_p"], f"{name}_sm", axis=-1),
        _node("MatMul", [f"{name}_p", v], [out], name),
    ]
    return nodes


@register_converter("flash_attention_qkv")
def _conv_flash_qkv(name, ins, out, attrs):
    """The op over the packed (B, L, 3U) projection exports as what
    ``MultiHeadAttention`` held there before it: reshape / transpose /
    slice into (B, H, L, D) heads, ``flash_attention``'s decomposition,
    transpose and reshape back — an exported graph is what it was."""
    shp = (attrs.get("_in_shapes") or [None])[0]
    if not shp:
        raise MXNetError(
            "onnx: flash_attention_qkv export needs input_shapes (to "
            "split the packed projection into heads)")
    B, L, U3 = (int(d) for d in shp)
    H = int(attrs["num_heads"])
    D = U3 // 3 // H
    nodes = _conv_reshape(f"{name}_split", ins[:1], f"{name}_split",
                          {"shape": (B, L, 3, H, D)})
    nodes += _conv_transpose(f"{name}_heads", [f"{name}_split"],
                             f"{name}_heads", {"axes": (2, 0, 3, 1, 4)})
    qkv = []
    for i, role in enumerate("qkv"):
        nodes += _conv_slice_axis(f"{name}_{role}5", [f"{name}_heads"],
                                  f"{name}_{role}5",
                                  {"axis": 0, "begin": i, "end": i + 1})
        nodes += _conv_reshape(f"{name}_{role}", [f"{name}_{role}5"],
                               f"{name}_{role}", {"shape": (B, H, L, D)})
        qkv.append(f"{name}_{role}")
    nodes += _conv_flash(f"{name}_attn", qkv + list(ins[1:]),
                         f"{name}_attn",
                         {"causal": attrs.get("causal"),
                          "_in_shapes": [(B, H, L, D)]})
    nodes += _conv_transpose(f"{name}_rows", [f"{name}_attn"],
                             f"{name}_rows", {"axes": (0, 2, 1, 3)})
    return nodes + _conv_reshape(name, [f"{name}_rows"], out,
                                 {"shape": (B, L, U3 // 3)})


for _mx, _onnx in [("broadcast_add", "Add"), ("broadcast_sub", "Sub"),
                   ("broadcast_mul", "Mul"), ("broadcast_div", "Div"),
                   ("broadcast_maximum", "Max"), ("broadcast_minimum", "Min"),
                   ("exp", "Exp"), ("log", "Log"), ("sqrt", "Sqrt"),
                   ("abs", "Abs"), ("negative", "Neg"), ("erf", "Erf"),
                   ("identity", "Identity"), ("BlockGrad", "Identity")]:
    def _make(onnx_name):
        def conv(name, ins, out, attrs):
            return [_node(onnx_name, ins, [out], name)]
        return conv
    register_converter(_mx)(_make(_onnx))


def _reduce_converter(onnx_name, axes_as_input):
    """sum/mean carry axis+keepdims; MXNet default keepdims=False differs
    from ONNX's keepdims=1, and opset 13 ReduceSum takes axes as an INPUT
    tensor while ReduceMean still uses the attr."""

    def conv(name, ins, out, attrs):
        axis = attrs.get("axis")
        if axis is not None and not isinstance(axis, (list, tuple)):
            axis = [axis]
        keepdims = 1 if attrs.get("keepdims") else 0
        if axes_as_input:
            if axis is None:
                return [_node(onnx_name, ins, [out], name,
                              keepdims=keepdims)]
            return [_node(onnx_name, ins + [f"{name}_axes"], [out], name,
                          keepdims=keepdims,
                          _const={f"{name}_axes":
                                  onp.asarray(axis, onp.int64)})]
        kw = {"keepdims": keepdims}
        if axis is not None:
            kw["axes"] = [int(a) for a in axis]
        return [_node(onnx_name, ins, [out], name, **kw)]

    return conv


register_converter("sum")(_reduce_converter("ReduceSum", axes_as_input=True))
register_converter("mean")(_reduce_converter("ReduceMean",
                                             axes_as_input=False))


# --------------------------------------------------------------------- #
# export driver
# --------------------------------------------------------------------- #

def _infer_node_shapes(sym, params, input_shapes, input_types):
    """Per-node output shapes via one eval_shape over the graph (the
    InferShape pass) — lets shape-dependent converters (flash_attention's
    1/sqrt(head_dim)) emit static constants.  Returns {} when inputs are
    underspecified; converters then degrade with explicit errors."""
    import jax

    from ..symbol.symbol import _topo, _node_outputs_abstract

    try:
        ishp = dict(input_shapes) if input_shapes else {}
        ityp = dict(input_types) if isinstance(input_types, (list, dict)) \
            else {}
        feed = {}
        for node in _topo(sym._heads):
            if node.op is not None:
                continue
            if node.name in params:
                v = params[node.name]
                arr = v.asnumpy() if hasattr(v, "asnumpy") \
                    else onp.asarray(v)
                feed[node.name] = jax.ShapeDtypeStruct(
                    arr.shape, onp.float32 if arr.dtype == onp.float64
                    else arr.dtype)
            else:
                if isinstance(input_types, (list, dict)):
                    dt = onp.dtype(str(ityp.get(node.name, "float32")))
                else:
                    dt = onp.dtype(str(input_types) if input_types
                                   else "float32")
                feed[node.name] = jax.ShapeDtypeStruct(
                    tuple(ishp[node.name]), dt)
        shapes = {}

        def run(*arrays):
            f = dict(zip(list(feed), arrays))
            memo = {}
            for node in _topo(sym._heads):
                if node.op is None:
                    memo[id(node)] = [f[node.name]]
                else:
                    ins = [memo[id(i)][idx] for i, idx in node.inputs]
                    memo[id(node)] = _node_outputs_abstract(node, ins)
                shapes[id(node)] = [tuple(o.shape)
                                    for o in memo[id(node)]]
            return [memo[id(n)][i] for n, i in sym._heads]

        jax.eval_shape(run, *feed.values())
        return shapes, None
    except Exception as e:
        # degrade (shape-dependent converters raise with this cause
        # attached) rather than failing every export for underspecified
        # inputs or a host-path op in the graph
        return {}, f"{type(e).__name__}: {e}"


def export_model(sym, params, input_shapes=None, input_types=None,
                 onnx_file_path="model.onnx", verbose=False, **kwargs):
    """Export (Symbol or exported json path, params dict or .params path)
    to ONNX (reference ``mx.onnx.export_model``)."""
    from ..symbol.symbol import Symbol, _topo
    from ..model import load_params_file
    from ..symbol import load as sym_load
    from ..ndarray import NDArray

    if isinstance(sym, str):
        sym = sym_load(sym)
    if not isinstance(sym, Symbol):
        raise MXNetError("export_model: sym must be a Symbol or json path")
    if isinstance(params, str):
        arg, aux = load_params_file(params)
        params = {**arg, **aux}

    node_shapes, shape_err = _infer_node_shapes(sym, params, input_shapes,
                                                input_types)
    nodes_out = []
    initializers = {}
    inputs = []
    # graph entry naming: node -> output names
    entry_name = {}
    for node in _topo(sym._heads):
        if node.op is None:
            entry_name[id(node)] = [node.name]
            if node.name in params:
                v = params[node.name]
                initializers[node.name] = (
                    v.asnumpy() if isinstance(v, NDArray) else onp.asarray(v))
            else:
                shp = None
                if input_shapes:
                    shp = dict(input_shapes).get(node.name) \
                        if isinstance(input_shapes, (list, dict)) else None
                dt = "float32"
                if input_types:
                    dt = str(dict(input_types).get(node.name, "float32")) \
                        if isinstance(input_types, (list, dict)) \
                        else str(input_types)
                inputs.append({"name": node.name,
                               "shape": list(shp) if shp else None,
                               "dtype": onp.dtype(dt).name})
            continue
        conv = _CONVERTERS.get(node.op)
        if conv is None:
            raise MXNetError(
                f"onnx: no converter registered for op {node.op!r} "
                f"({sorted(_CONVERTERS)} available)")
        in_names = [entry_name[id(i)][idx] for i, idx in node.inputs]
        n_out = node.num_outputs or 1
        out_names = [node.name if n_out == 1 else f"{node.name}_out{i}"
                     for i in range(n_out)]
        entry_name[id(node)] = out_names
        attrs = node.attrs
        if node_shapes:
            attrs = {**attrs,
                     "_in_shapes": [node_shapes[id(i)][idx]
                                    for i, idx in node.inputs]}
        try:
            produced = conv(node.name, in_names, out_names[0], attrs)
        except MXNetError as e:
            if shape_err and ("input_shapes" in str(e)
                              or "_in_shapes" in str(e)):
                raise MXNetError(
                    f"{e}  (note: the InferShape pass failed with: "
                    f"{shape_err})") from e
            raise
        for p in produced:
            consts = p["attrs"].pop("_const", None)
            if consts:
                initializers.update(consts)
            nodes_out.append(p)

    outputs = [entry_name[id(n)][i] for n, i in sym._heads]
    graph = {
        "ir_version": 8,
        "opset": _OPSET,
        "producer": "mxnet_tpu",
        "graph": {
            "nodes": nodes_out,
            "inputs": inputs,
            "outputs": [{"name": o} for o in outputs],
            "initializers": {k: {"shape": list(v.shape),
                                 "dtype": str(v.dtype),
                                 "data": v.reshape(-1).tolist()}
                             for k, v in initializers.items()},
        },
    }
    try:
        import onnx  # noqa: F401
        return _write_protobuf(graph, initializers, onnx_file_path)
    except ImportError:
        path = onnx_file_path if onnx_file_path.endswith(".json") \
            else onnx_file_path + ".json"
        with open(path, "w") as f:
            json.dump(graph, f)
        if verbose:
            print(f"onnx package unavailable; wrote JSON container {path}")
        return path


def _write_protobuf(graph, initializers, path):
    import onnx
    from onnx import helper, numpy_helper, TensorProto
    nodes = [helper.make_node(n["op_type"], n["inputs"], n["outputs"],
                              name=n["name"], **n["attrs"])
             for n in graph["graph"]["nodes"]]
    inits = [numpy_helper.from_array(v, name=k)
             for k, v in initializers.items()]
    from onnx import mapping
    dtype_enum = {onp.dtype(k).name: v
                  for k, v in mapping.NP_TYPE_TO_TENSOR_TYPE.items()} \
        if hasattr(mapping, "NP_TYPE_TO_TENSOR_TYPE") else {}

    def _enum(dt):
        return dtype_enum.get(onp.dtype(dt).name, TensorProto.FLOAT)

    ins = [helper.make_tensor_value_info(
        i["name"], _enum(i.get("dtype", "float32")), i["shape"])
        for i in graph["graph"]["inputs"]]
    outs = [helper.make_tensor_value_info(o["name"], TensorProto.FLOAT, None)
            for o in graph["graph"]["outputs"]]
    g = helper.make_graph(nodes, "mxnet_tpu", ins, outs, initializer=inits)
    model = helper.make_model(
        g, opset_imports=[helper.make_opsetid("", graph["opset"])])
    onnx.save(model, path)
    return path
