"""Graph IR: the TPU-native equivalent of NNVM's node graph.

Reference: NNVM Graph/Node/NodeEntry (3rdparty/tvm/nnvm, used by
src/executor/graph_executor.cc and src/imperative/cached_op.cc).

TPU-native design: the graph is a tiny pure-Python DAG whose nodes hold
registered ops; "lowering" is building ONE jax-traceable Python function
over the whole graph and handing it to jax.jit. XLA then subsumes every
NNVM pass the reference runs at bind time: PlanMemory -> buffer assignment,
DetectInplaceAddTo -> fusion, AttachOpExecs/bulking -> single compiled
computation, PlaceDevice -> sharding annotations.

The same builder serves the Executor (Module/symbolic path), CachedOp
(Gluon hybridize path) and Symbol.eval.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .base import MXNetError
from .compile import programs as _programs
from .ops import registry as _reg


class Node:
    """One graph node: a variable (op is None) or an op application.

    inputs: list of (Node, output_index) edges.
    """

    __slots__ = ("op", "inputs", "params", "name", "attrs", "is_aux",
                 "__weakref__")

    def __init__(self, op, inputs, params, name, is_aux=False, attrs=None):
        self.op = op
        self.inputs = inputs
        self.params = params
        self.name = name
        self.is_aux = is_aux
        self.attrs = attrs or {}

    @property
    def is_variable(self):
        return self.op is None

    def n_visible(self):
        if self.op is None:
            return 1
        vis = self.op.visible_outputs
        if callable(vis):
            return vis(self.params)
        return vis or self.op.out_arity(self.params)

    def n_raw(self):
        if self.op is None:
            return 1
        return self.op.out_arity(self.params)

    def __repr__(self):
        if self.op is None:
            return "Var(%s)" % self.name
        return "Node(%s:%s)" % (self.op.name, self.name)


def topo_order(output_entries):
    """Topological order of all nodes reachable from (node, idx) entries.
    Iterative DFS (the reference's NNVM PostOrderDFSVisit)."""
    order = []
    seen = set()
    stack = [(n, False) for n, _ in reversed(output_entries)]
    while stack:
        node, expanded = stack.pop()
        if id(node) in seen:
            continue
        if expanded:
            seen.add(id(node))
            order.append(node)
        else:
            stack.append((node, True))
            for inp, _ in reversed(node.inputs):
                if id(inp) not in seen:
                    stack.append((inp, False))
    return order


def aux_var_ids(order):
    """Variables consumed at aux input positions of some op IN THIS GRAPH.

    Aux-ness is a property of usage within a graph, not of the variable
    node itself — the same var symbol can be a plain argument in one graph
    and a BatchNorm moving-stat in another (reference: aux states are
    declared per-op by ListAuxiliaryStates, resolved per-graph)."""
    aux = set()
    for node in order:
        if node.is_variable or not node.op.aux_write:
            continue
        for _, ii in node.op.aux_write.items():
            in_node, _ = node.inputs[ii]
            if in_node.is_variable:
                aux.add(id(in_node))
    return aux


REMAT_ATTR = "__remat__"        # a node's group of rematerialisation
# what an op names so (`jax.ad_checkpoint.checkpoint_name`) a group keeps
# from its first pass in place of computing it again: an output that is
# dear to recompute and cheap to hold
REMAT_KEEP = "mx.keep"


def _group_checkpoint():
    """How a remat group runs: it keeps what enters and leaves it and what
    its ops name `REMAT_KEEP`. Each group gets a policy object of its own,
    as it always has: JAX lowers the callees of groups that share one
    together, which would change the step program's text."""
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(REMAT_KEEP))


def _stretches(units, checkpoint):
    """Merge each run of consecutive units that belong to no group into
    one unit run under `checkpoint`; variables drop out. A run of
    consecutive units of a topological order is closed: nothing outside
    it lies on a path between two of its nodes."""
    out, stretch = [], None
    for unit_checkpoint, nodes in units:
        nodes = [n for n in nodes if not n.is_variable]
        if not nodes:
            continue
        if unit_checkpoint is not None:
            out.append((unit_checkpoint, nodes))
            stretch = None
        elif stretch is None:
            stretch = list(nodes)
            out.append((checkpoint, stretch))
        else:
            stretch.extend(nodes)
    return out


def counter_vars(output_entries):
    """{aux variable name: (metric name, ...)} for the aux inputs that an
    op of this graph declares as device counters (`Op.counters`)."""
    out = {}
    for node in topo_order(output_entries):
        if node.is_variable or not node.op.counters:
            continue
        for ii, names in node.op.counters.items():
            in_node, _ = node.inputs[ii]
            if in_node.is_variable:
                out[in_node.name] = tuple(names)
    return out


def _remat_units(order):
    """Cut `order` into units of execution: a node alone, or all the nodes
    that carry one `__remat__` mark (gluon.block.remat_scope). Returns
    [(mark or None, [nodes])] in an order in which every unit comes after
    the units it reads, or `None` where no node is marked. A marked group
    that another unit both reads and feeds cannot run as one function:
    that raises."""
    mark = {id(n): n.attrs.get(REMAT_ATTR) for n in order
            if not n.is_variable}
    if not any(mark.values()):
        return None
    unit_of, members = {}, {}
    for n in order:
        u = mark.get(id(n)) or id(n)
        unit_of[id(n)] = u
        members.setdefault(u, []).append(n)
    reads = {u: [r for r in dict.fromkeys(
        unit_of[id(i)] for n in nodes for i, _ in n.inputs) if r != u]
        for u, nodes in members.items()}
    done, out, visiting = set(), [], set()
    for start in members:                  # dict order: first appearance
        stack = [(start, False)]
        while stack:
            u, expanded = stack.pop()
            if u in done:
                continue
            if expanded:
                visiting.discard(u)
                done.add(u)
                out.append((u if isinstance(u, str) else None, members[u]))
                continue
            if u in visiting:
                raise MXNetError(
                    "remat group %r is not closed: a node outside it lies "
                    "on a path between two of its nodes" % (u,))
            visiting.add(u)
            stack.append((u, True))
            stack.extend((r, False) for r in reads[u] if r not in done)
    return out


def collect_vars(output_entries):
    """Return (arg_nodes, aux_nodes) in first-seen topo order."""
    order = topo_order(output_entries)
    aux_ids = aux_var_ids(order)
    args, aux = [], []
    for node in order:
        if node.is_variable:
            (aux if id(node) in aux_ids else args).append(node)
    return args, aux


def build_graph_fn(output_entries, mode="predict", policy=None):
    """Build a pure jax function evaluating the graph.

    Returns (fn, arg_names, aux_names, needs_rng) where::

        fn(args: dict[str, array], aux: dict[str, array], key)
            -> (list[array] outputs, dict[str, array] aux_updates)

    aux_updates carries new values for mutable aux states (BatchNorm moving
    stats) — the functional-state threading that replaces the reference's
    in-place aux mutation (src/operator/nn/batch_norm.cc writes aux_states
    in place; XLA state must be explicit).

    `policy`, a `jax.checkpoint` policy, runs each stretch of nodes that
    lies outside every remat group as one `jax.checkpoint` under it
    (CachedOp's recorded forward, whose backward is a program of its own:
    nothing to keep apart from the forward, so no `prevent_cse`). The
    groups stay checkpoints of their own beside them, not inside: a
    checkpoint inside another is differentiated under the outer one's
    policy.
    """
    order = topo_order(output_entries)
    aux_ids = aux_var_ids(order)
    arg_nodes, aux_nodes = collect_vars(output_entries)
    arg_names = [n.name for n in arg_nodes]
    aux_names = [n.name for n in aux_nodes]
    needs_rng = any((not n.is_variable) and n.op.needs_rng for n in order)

    # precompute per-node static params (defaults applied once)
    node_params = {}
    for node in order:
        if node.is_variable:
            continue
        p = _reg.apply_defaults(node.op, node.params)
        if node.op.takes_mode:
            p["_mode"] = mode
        node_params[id(node)] = p
    # every node's ops carry `mx.<op>.<node>` in their HLO metadata, so
    # a device trace's instructions have an owner (compile/programs.py);
    # JAX writes `transpose(jvp(...))` around it for the backward ops
    node_scope = {id(node): ("mx.%s.%s" % (node.op.name, node.name))
                  .replace("/", "_")
                  for node in order if not node.is_variable}

    train = mode == "train"

    def run(nodes, values, aux_updates, key):
        """Evaluate `nodes` in order into `values`; returns the key."""
        for node in nodes:
            arrs = [values[id(n)][i] for n, i in node.inputs]
            op = node.op
            if op.needs_rng:
                if key is None:
                    raise MXNetError(
                        "graph contains random op %s but no PRNG key was "
                        "provided" % op.name)
                key, sub = jax.random.split(key)
                arrs = [sub] + arrs
            with _programs.scope(node_scope[id(node)]):
                raw = op.fn(*arrs, **node_params[id(node)])
            if not isinstance(raw, tuple):
                raw = (raw,)
            values[id(node)] = raw
            if op.aux_write and train:
                for oi, ii in op.aux_write.items():
                    in_node, _ = node.inputs[ii]
                    if in_node.is_variable and id(in_node) in aux_ids:
                        aux_updates[in_node.name] = raw[oi]
        return key

    # rematerialisation by group (gluon.block.remat_scope): the marked
    # nodes of one group run as one `jax.checkpoint`, which keeps the
    # group's inputs and what leaves it, and computes the inside again in
    # the backward pass. A graph with no mark runs node by node as ever.
    # Units are [(checkpoint or None, nodes)]: None runs node by node.
    units = _remat_units(order) if train else None
    if units is not None:
        units = [(_group_checkpoint() if mark else None, nodes)
                 for mark, nodes in units if not nodes[0].is_variable]
    if policy is not None:
        units = _stretches(units or [(None, order)], functools.partial(
            jax.checkpoint, policy=policy, prevent_cse=False))
    used_outside = set()
    if units is not None:
        group_of = {id(n): g for g, (p, nodes) in enumerate(units)
                    if p is not None for n in nodes}
        for node in order:
            for n, i in node.inputs:
                g = group_of.get(id(n))
                if g is not None and g != group_of.get(id(node)):
                    used_outside.add((id(n), i))
        used_outside.update((id(n), i) for n, i in output_entries
                            if id(n) in group_of)

    def run_group(nodes, values, aux_updates, key, checkpoint):
        inside = {id(n) for n in nodes}
        # in the order the group first reads them: the same program in
        # every process (an order by id() would miss the compile cache)
        ext = list(dict.fromkeys((id(n), i) for node in nodes
                                 for n, i in node.inputs
                                 if id(n) not in inside))
        leaving = [(id(n), i) for n in nodes for i in range(n.n_raw())
                   if (id(n), i) in used_outside]

        @checkpoint
        def group(ext_vals, key):
            vals, ups = {}, {}
            for (nid, i), v in zip(ext, ext_vals):
                vals.setdefault(nid, {})[i] = v
            key = run(nodes, vals, ups, key)
            return [vals[nid][i] for nid, i in leaving], ups, key

        outs, ups, key = group([values[nid][i] for nid, i in ext], key)
        for (nid, i), v in zip(leaving, outs):
            values.setdefault(nid, {})[i] = v
        aux_updates.update(ups)
        return key

    def fn(args, aux, key=None):
        values = {}
        aux_updates = {}
        for node in order:
            if node.is_variable:
                values[id(node)] = ((aux if id(node) in aux_ids else args)
                                    [node.name],)
        if units is None:
            run([n for n in order if not n.is_variable], values,
                aux_updates, key)
        else:
            for checkpoint, nodes in units:
                if checkpoint is not None:
                    key = run_group(nodes, values, aux_updates, key,
                                    checkpoint)
                else:
                    key = run(nodes, values, aux_updates, key)
        outs = [values[id(n)][i] for n, i in output_entries]
        return outs, aux_updates

    return fn, arg_names, aux_names, needs_rng


# ---------------------------------------------------------------------------
# shape/dtype inference (reference: src/executor/infer_graph_attr_pass.cc).
# Forward-propagates jax.ShapeDtypeStruct through the graph; parameter
# variables with unknown shape are resolved by per-op rules (the analog of
# the reference's per-op FInferShape filling in weight shapes).
# ---------------------------------------------------------------------------

# op name -> rule(in_structs, params, in_nodes) -> list in_structs (completed)
_PARAM_SHAPE_RULES = {}


def register_shape_rule(name):
    def deco(fn):
        _PARAM_SHAPE_RULES[name] = fn
        return fn
    return deco


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _f32_like(in_structs):
    for s in in_structs:
        if s is not None:
            return s.dtype
    return jnp.float32


@register_shape_rule("FullyConnected")
def _fc_rule(ins, params, nodes):
    data = ins[0]
    if data is None:
        return ins
    dt = data.dtype
    if params.get("flatten", True) and len(data.shape) > 1:
        in_units = 1
        for s in data.shape[1:]:
            in_units *= int(s)
    else:
        in_units = data.shape[-1]
    nh = params["num_hidden"]
    out = list(ins)
    if out[1] is None:
        out[1] = _struct((nh, in_units), dt)
    if len(out) > 2 and out[2] is None:
        out[2] = _struct((nh,), dt)
    return out


@register_shape_rule("Convolution")
def _conv_rule(ins, params, nodes):
    data = ins[0]
    if data is None:
        return ins
    dt = data.dtype
    kernel = tuple(params["kernel"]) if not isinstance(params["kernel"], int) \
        else (params["kernel"],)
    nf = params["num_filter"]
    ng = params.get("num_group", 1) or 1
    from .ops.nn import is_channels_last, channel_axis
    layout = params.get("layout")
    channels_last = is_channels_last(layout)
    c_axis = channel_axis(layout, len(data.shape))
    cin = data.shape[c_axis]
    out = list(ins)
    if out[1] is None:
        # channels-last weight is (O, *kernel, I) per the NHWC convention
        wshape = (nf,) + kernel + (cin // ng,) if channels_last \
            else (nf, cin // ng) + kernel
        out[1] = _struct(wshape, dt)
    if len(out) > 2 and out[2] is None:
        out[2] = _struct((nf,), dt)
    return out


@register_shape_rule("Deconvolution")
def _deconv_rule(ins, params, nodes):
    data = ins[0]
    if data is None:
        return ins
    dt = data.dtype
    kernel = tuple(params["kernel"])
    nf = params["num_filter"]
    ng = params.get("num_group", 1) or 1
    cin = data.shape[1]
    out = list(ins)
    if out[1] is None:
        out[1] = _struct((cin, nf // ng) + kernel, dt)
    if len(out) > 2 and out[2] is None:
        out[2] = _struct((nf,), dt)
    return out


def _norm_rule_factory(n_stats):
    def rule(ins, params, nodes):
        data = ins[0]
        if data is None:
            return ins
        axis = params.get("axis", 1)
        c = data.shape[axis % len(data.shape)]
        out = list(ins)
        for i in range(1, min(len(out), 1 + n_stats)):
            if out[i] is None:
                out[i] = _struct((c,), jnp.float32)
        return out
    return rule


_PARAM_SHAPE_RULES["BatchNorm"] = _norm_rule_factory(4)
_PARAM_SHAPE_RULES["BatchNorm_v1"] = _norm_rule_factory(4)
_PARAM_SHAPE_RULES["InstanceNorm"] = _norm_rule_factory(2)


@register_shape_rule("LayerNorm")
def _ln_rule(ins, params, nodes):
    data = ins[0]
    if data is None:
        return ins
    axis = params.get("axis", -1)
    c = data.shape[axis % len(data.shape)]
    out = list(ins)
    for i in (1, 2):
        if i < len(out) and out[i] is None:
            out[i] = _struct((c,), data.dtype)
    return out


@register_shape_rule("Embedding")
def _emb_rule(ins, params, nodes):
    out = list(ins)
    if out[1] is None:
        out[1] = _struct((params["input_dim"], params["output_dim"]),
                         jnp.float32)
    return out


@register_shape_rule("LeakyReLU")
def _prelu_rule(ins, params, nodes):
    if params.get("act_type") != "prelu" or len(ins) < 2:
        return ins
    data = ins[0]
    if data is None or ins[1] is not None:
        return ins
    out = list(ins)
    c = data.shape[1] if len(data.shape) > 1 else 1
    out[1] = _struct((c,), data.dtype)
    return out


@register_shape_rule("RNN")
def _rnn_rule(ins, params, nodes):
    from .ops.nn import rnn_param_size
    data = ins[0]
    if data is None:
        return ins
    dt = data.dtype
    T, B, input_size = data.shape
    H = params["state_size"]
    L = params["num_layers"]
    bi = params.get("bidirectional", False)
    d = 2 if bi else 1
    out = list(ins)
    if out[1] is None:
        out[1] = _struct(
            (rnn_param_size(L, input_size, H, bi, params.get("mode", "lstm")),),
            dt)
    for i in range(2, len(out)):
        if out[i] is None:
            out[i] = _struct((L * d, B, H), dt)
    return out


@register_shape_rule("SoftmaxOutput")
def _softmax_out_rule(ins, params, nodes):
    data = ins[0]
    if data is None or len(ins) < 2 or ins[1] is not None:
        return ins
    out = list(ins)
    if params.get("multi_output"):
        lbl = (data.shape[0],) + tuple(data.shape[2:])
    elif params.get("preserve_shape"):
        lbl = tuple(data.shape[:-1])
    else:
        lbl = (data.shape[0],)
    out[1] = _struct(lbl, jnp.float32)
    return out


def _regression_rule(ins, params, nodes):
    data = ins[0]
    if data is None or len(ins) < 2 or ins[1] is not None:
        return ins
    out = list(ins)
    out[1] = _struct(data.shape, data.dtype)
    return out


@register_shape_rule("SVMOutput")
def _svm_out_rule(ins, params, nodes):
    """label is one class index per row (reference: svm_output.cc)."""
    data = ins[0]
    if data is None or len(ins) < 2 or ins[1] is not None:
        return ins
    out = list(ins)
    out[1] = _struct((data.shape[0],), jnp.float32)
    return out


for _n in ("LinearRegressionOutput", "MAERegressionOutput",
           "LogisticRegressionOutput"):
    _PARAM_SHAPE_RULES[_n] = _regression_rule


def infer_structs(output_entries, known, mode="predict"):
    """Propagate ShapeDtypeStructs through the graph.

    known: dict var_name -> ShapeDtypeStruct (or (shape, dtype)).
    Returns dict: var_name -> struct for every variable it could resolve,
    plus a dict node-id -> list of output structs.
    """
    norm = {}
    for k, v in known.items():
        if isinstance(v, jax.ShapeDtypeStruct):
            norm[k] = v
        elif isinstance(v, tuple) and v and isinstance(v[0], (tuple, list)):
            norm[k] = _struct(v[0], v[1])
        else:
            norm[k] = _struct(v, jnp.float32)
    known = norm

    order = topo_order(output_entries)
    var_structs = dict(known)
    out_structs = {}

    for node in order:
        if node.is_variable:
            s = var_structs.get(node.name)
            out_structs[id(node)] = [s]
            continue
        ins = [out_structs[id(n)][i] for n, i in node.inputs]
        rule = _PARAM_SHAPE_RULES.get(node.op.name)
        if rule is not None and any(s is None for s in ins):
            ins = rule(ins, _reg.apply_defaults(node.op, node.params),
                       [n for n, _ in node.inputs])
            # write resolved structs back onto variable inputs
            for (in_node, _), s in zip(node.inputs, ins):
                if in_node.is_variable and s is not None and \
                        var_structs.get(in_node.name) is None:
                    var_structs[in_node.name] = s
                    out_structs[id(in_node)] = [s]
        if any(s is None for s in ins):
            out_structs[id(node)] = [None] * node.n_raw()
            continue
        params = _reg.apply_defaults(node.op, node.params)
        if node.op.takes_mode:
            params["_mode"] = mode
        args = list(ins)
        if node.op.needs_rng:
            args = [jax.ShapeDtypeStruct((2,), jnp.uint32)] + args
        try:
            raw = jax.eval_shape(lambda *a, _p=params, _f=node.op.fn:
                                 _f(*a, **_p), *args)
        except Exception as e:  # pragma: no cover - surface as infer error
            raise MXNetError(
                "shape inference failed at op %s(%s): %s"
                % (node.op.name, node.name, e)) from None
        if not isinstance(raw, tuple):
            raw = (raw,)
        out_structs[id(node)] = list(raw)

    return var_structs, out_structs
