"""Imperative autograd: record/pause scopes + tape backward.

Reference: python/mxnet/autograd.py and src/imperative/imperative.cc
(RecordOp :183, Backward :270). The reference builds an NNVM gradient graph
and replays it through the engine; here each recorded op carries a jax.vjp
closure (an XLA-compiled pullback), and backward() walks the tape in
reverse topological order accumulating cotangents. Gradients of jitted
graphs (CachedOp / Executor) don't use this tape at all — they are computed
by jax.grad over the whole traced function, which is the TPU-idiomatic path.
"""
from __future__ import annotations

import threading

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .observability.trace import trace_span

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    prev = _st().recording
    _state.recording = bool(is_record)
    return prev


def set_training(train_mode):
    prev = _st().training
    _state.training = bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._record = is_record
        self._train = train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._record is not None:
            st.recording = self._record
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *exc):
        _state.recording, _state.training = self._prev
        return False


def record(train_mode=True):
    """Scope in which executed ops are recorded for backward."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# tape
# ---------------------------------------------------------------------------


class _TapeNode:
    __slots__ = ("op", "inputs", "vjp_fn", "n_raw", "visible", "out_avals",
                 "replay", "in_arrays", "rng_key")

    def __init__(self, op, inputs, vjp_fn, n_raw, visible, out_avals=(),
                 replay=None, in_arrays=None, rng_key=None):
        self.op = op
        self.inputs = inputs      # list of NDArray (strong refs)
        self.vjp_fn = vjp_fn
        self.n_raw = n_raw        # raw output arity (incl. hidden aux)
        self.visible = visible
        # (shape, dtype) per raw output — needed to zero-fill cotangent
        # slots of unused outputs (vjp wants the full output pytree)
        self.out_avals = out_avals
        # pure forward closure + its record-time input arrays: lets
        # grad(create_graph=True) replay the subgraph as a pure JAX
        # function, so higher-order derivatives compose through jax.vjp
        # instead of needing a tape-of-tapes.
        self.replay = replay
        self.in_arrays = in_arrays
        self.rng_key = rng_key    # key consumed at record time, for replay


def _record(op, inputs, outputs, raw, vjp_fn, replay=None, in_arrays=None,
            rng_key=None):
    """Called by ndarray.invoke under record scope."""
    node = _TapeNode(op, list(inputs), vjp_fn, len(raw), len(outputs),
                     out_avals=[(r.shape, r.dtype) for r in raw],
                     replay=replay, in_arrays=in_arrays, rng_key=rng_key)
    for i, out in enumerate(outputs):
        out._tape_node = node
        out._tape_index = i


def mark_variables(variables, gradients=None, grad_reqs="write"):
    """Attach gradient buffers to arrays (reference: autograd.py:197)."""
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
    if gradients is None:
        gradients = [None] * len(variables)
    if not isinstance(gradients, (list, tuple)):
        gradients = [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v.attach_grad(grad_req=req)
        if g is not None:
            v._grad._data = g._data


def _is_float0(x):
    return x.dtype == jax.dtypes.float0


def _walk(heads, head_grads, retain_graph, collect_for=None):
    """Reverse-topological cotangent propagation.

    collect_for: optional list of NDArrays — return their grads instead of
    (in addition to) writing into attached .grad buffers.
    """
    from .ndarray.ndarray import NDArray

    # seed cotangents per node
    node_cots = {}   # node -> list of cotangent arrays per raw output
    leaf_grads = {}  # id(ndarray) -> (ndarray, accumulated jax array)

    def seed(nd, g):
        node = nd._tape_node
        if node is None:
            # head is a leaf: its own grad is the seed
            if nd._grad is not None or collect_for is not None:
                acc = leaf_grads.get(id(nd))
                leaf_grads[id(nd)] = (nd, g if acc is None else acc[1] + g)
            return
        cots = node_cots.setdefault(node, [None] * node.n_raw)
        idx = nd._tape_index
        cots[idx] = g if cots[idx] is None else cots[idx] + g

    for nd, g in zip(heads, head_grads):
        if nd._tape_node is None and nd._grad is None and collect_for is None:
            raise MXNetError(
                "cannot differentiate: output is not in the recorded graph "
                "(was it computed under autograd.record()?)")
        seed(nd, g)

    # topo order over nodes reachable from heads (iterative: recorded
    # chains can exceed Python's recursion limit)
    order = []
    seen = set()

    def dfs(root):
        if root is None or id(root) in seen:
            return
        stack = [(root, False)]
        while stack:
            n, expanded = stack.pop()
            if expanded:
                order.append(n)
                continue
            if id(n) in seen:
                continue
            seen.add(id(n))
            stack.append((n, True))
            for inp in n.inputs:
                if isinstance(inp, NDArray) and inp._tape_node is not None \
                        and id(inp._tape_node) not in seen:
                    stack.append((inp._tape_node, False))

    for nd in heads:
        dfs(nd._tape_node)

    for node in reversed(order):
        cots = node_cots.get(node)
        if cots is None:
            continue
        if node.vjp_fn is None:
            raise MXNetError(
                "backward: graph was already freed "
                "(pass retain_graph=True to backward() to reuse it)")
        # fill missing output cotangents with zeros: vjp needs all of them
        filled = [c if c is not None else jnp.zeros(sh, dt)
                  for c, (sh, dt) in zip(cots, node.out_avals)]
        in_cots = node.vjp_fn(tuple(filled))
        offset = 1 if node.op.needs_rng else 0
        for j, inp in enumerate(node.inputs):
            g = in_cots[j + offset]
            if g is None or _is_float0(g):
                continue
            if not isinstance(inp, NDArray):
                continue
            if inp._tape_node is not None:
                cc = node_cots.setdefault(inp._tape_node,
                                          [None] * inp._tape_node.n_raw)
                idx = inp._tape_index
                cc[idx] = g if cc[idx] is None else cc[idx] + g
            if inp._grad is not None or collect_for is not None:
                acc = leaf_grads.get(id(inp))
                leaf_grads[id(inp)] = (inp, g if acc is None else acc[1] + g)
        if not retain_graph:
            node.vjp_fn = None

    # write into .grad buffers; the freshness mark backs
    # Trainer.step(ignore_stale_grad=True) — only a backward pass makes
    # a grad "fresh" (the reference's _fresh_grad contract;
    # zero_grad/manual writes do not)
    for _, (nd, g) in leaf_grads.items():
        if nd._grad is not None:
            if nd._grad_req == "add":
                nd._grad._data = nd._grad._data + g
                nd._grad._fresh_grad = True
            elif nd._grad_req != "null":
                nd._grad._data = g
                nd._grad._fresh_grad = True

    if collect_for is not None:
        out = []
        for v in collect_for:
            ent = leaf_grads.get(id(v))
            out.append(None if ent is None else ent[1])
        return out
    return None


def _normalize_head_grads(heads, head_grads):
    """Shared output-cotangent seeding: ones for None, unwrap NDArrays."""
    if head_grads is None:
        return [jnp.ones_like(h._data) for h in heads]
    if not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    return [jnp.ones_like(h._data) if g is None else g._data
            for h, g in zip(heads, head_grads)]


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. all marked variables
    (reference: autograd.py:243)."""
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    with trace_span("frontend.backward"):
        _walk(heads, _normalize_head_grads(heads, head_grads),
              retain_graph)


def _build_head_fn(heads, variables):
    """Reconstruct the recorded subgraph between `variables` and `heads` as a
    pure function var_arrays -> tuple(head_arrays).

    This is the TPU-native path to higher-order autograd: rather than taping
    the backward pass (the reference's NNVM approach, autograd.py:270 /
    imperative.cc:270), we replay the forward as a traceable JAX function and
    let jax.vjp compose to any derivative order.

    Only the variable-dependent subgraph is replayed; branches constant
    w.r.t. the variables fold to their record-time values (so constant
    branches may contain non-replayable nodes, e.g. custom Functions).
    Returns (head_fn, recorded_var_vals, extras):
      - recorded_var_vals maps each reachable variable to its record-time
        value; a variable absent from it is unreachable from the heads;
      - extras is a list of (ndarray, recorded_value) for every OTHER
        differentiable leaf the replayed subgraph reads (weights, inputs,
        tape intermediates). head_fn takes var_vals + extra_vals, so the
        recorded gradient keeps cotangent paths into those leaves — e.g.
        the WGAN-GP pattern (penalty = |dL/dx|²) must still backprop into
        the weights, which are extras here, not listed variables.
    """
    from .ndarray.ndarray import NDArray

    var_ids = {id(v): v for v in variables}
    full_order, seen = [], set()

    # iterative post-order DFS: recorded chains can be 1000s of ops deep
    # (unrolled RNNs), past Python's recursion limit
    def dfs(root):
        node = root._tape_node
        if node is None or id(node) in seen:
            return
        stack = [(node, False)]
        while stack:
            n, expanded = stack.pop()
            if expanded:
                full_order.append(n)
                continue
            if id(n) in seen:
                continue
            seen.add(id(n))
            stack.append((n, True))
            for inp in n.inputs:
                if isinstance(inp, NDArray) and id(inp) not in var_ids:
                    n2 = inp._tape_node
                    if n2 is not None and id(n2) not in seen:
                        stack.append((n2, False))

    for h in heads:
        if id(h) not in var_ids:
            dfs(h)

    # variable-dependence analysis: only dependent nodes are replayed;
    # everything else folds to its recorded value
    dependent = set()
    recorded_var_vals = {}
    for node in full_order:
        for j, inp in enumerate(node.inputs):
            if not isinstance(inp, NDArray):
                continue
            if id(inp) in var_ids:
                dependent.add(id(node))
                # the value this consumer saw at record time — later in-place
                # mutation of the variable must not change the answer
                val = (node.in_arrays[j] if node.in_arrays is not None
                       else inp._data)
                prev = recorded_var_vals.setdefault(id(inp), val)
                # identity check: a variable rebound between two recorded
                # uses has no single replay value — refuse rather than
                # silently differentiate at the first-seen one
                if prev is not val:
                    raise MXNetError(
                        "autograd.grad(create_graph=True): variable was "
                        "mutated in place between recorded uses; the "
                        "replayed graph has no consistent value for it")
            elif inp._tape_node is not None and \
                    id(inp._tape_node) in dependent:
                dependent.add(id(node))
    order = [n for n in full_order if id(n) in dependent]

    for node in order:
        if node.replay is None:
            raise MXNetError(
                "autograd.grad(create_graph=True): the variable-dependent "
                "subgraph contains a node ('%s') that cannot be replayed "
                "(custom autograd.Function and subgraph control-flow ops "
                "record opaque backward closures). Higher-order gradients "
                "require pure-JAX replayable ops on the path from the "
                "variables to the heads." % getattr(node.op, "name", "?"))

    # other differentiable leaves read by the replayed subgraph: an
    # NDArray input with a grad buffer, or produced by a NON-replayed
    # (variable-independent) tape node, must stay a function argument
    # (not a folded constant) so later backward()/grad() over the
    # returned gradients can reach it. Intermediates produced by
    # replayed nodes are recomputed, never arguments.
    extras, extra_seen = [], set()
    for node in order:
        for j, inp in enumerate(node.inputs):
            if (not isinstance(inp, NDArray) or id(inp) in var_ids
                    or id(inp) in extra_seen):
                continue
            produced_by_replay = (inp._tape_node is not None
                                  and id(inp._tape_node) in dependent)
            if produced_by_replay:
                continue
            if inp._tape_node is not None or inp._grad is not None:
                extra_seen.add(id(inp))
                val = (node.in_arrays[j] if node.in_arrays is not None
                       else inp._data)
                extras.append((inp, val))

    for h in heads:  # a head that IS a variable depends on it trivially
        if id(h) in var_ids:
            recorded_var_vals.setdefault(id(h), h._data)

    n_vars = len(variables)

    def head_fn(*vals):
        env = {id(v): val for v, val in zip(variables, vals[:n_vars])}
        for (leaf, _), val in zip(extras, vals[n_vars:]):
            env[id(leaf)] = val
        node_out = {}

        def in_val(node, j, inp):
            if isinstance(inp, NDArray):
                if id(inp) in env:
                    return env[id(inp)]
                n2 = inp._tape_node
                if n2 is not None and id(n2) in node_out:
                    return node_out[id(n2)][inp._tape_index]
            # constant w.r.t. the variables: value captured at record time
            return node.in_arrays[j]

        for node in order:
            arrs = [in_val(node, j, inp) for j, inp in enumerate(node.inputs)]
            if node.rng_key is not None:
                arrs = [node.rng_key] + arrs
            out = node.replay(*arrs)
            node_out[id(node)] = out if isinstance(out, tuple) else (out,)

        outs = []
        for h in heads:
            if id(h) in env:
                outs.append(env[id(h)])
            elif h._tape_node is not None and id(h._tape_node) in node_out:
                outs.append(node_out[id(h._tape_node)][h._tape_index])
            else:
                outs.append(h._data)
        return tuple(outs)

    return head_fn, recorded_var_vals, extras


class _GradOp:
    needs_rng = False
    name = "_autograd_grad"


def _grad_create_graph(heads, variables, head_grads):
    """grad() with create_graph=True: differentiable gradients.

    Computes d(heads)/d(variables) via jax.vjp over the replayed forward and
    records the result on the tape (with a replayable closure of its own), so
    backward()/grad() over the returned gradients — at any order — just work.
    """
    from .ndarray.ndarray import NDArray

    # dedupe: a variable listed twice gets the same (full) gradient in every
    # position, matching the tape path's collect_for semantics
    uniq, pos = [], []
    index_of = {}
    for v in variables:
        if id(v) not in index_of:
            index_of[id(v)] = len(uniq)
            uniq.append(v)
        pos.append(index_of[id(v)])

    head_fn, recorded_vals, extras = _build_head_fn(heads, uniq)
    for v in uniq:
        if id(v) not in recorded_vals:
            raise MXNetError("autograd.grad: a variable is unreachable "
                             "from the heads")
    n_vars = len(uniq)
    all_inputs = list(uniq) + [leaf for leaf, _ in extras]
    all_vals = tuple([recorded_vals[id(v)] for v in uniq]
                     + [val for _, val in extras])
    hg = tuple(head_grads)

    def grad_fn(*vals):
        # gradients w.r.t. the listed variables only, but as a function of
        # ALL differentiable leaves so their cotangent paths survive
        _, pull = jax.vjp(head_fn, *vals)
        return tuple(pull(hg)[:n_vars])

    out_vals, pullback = jax.vjp(grad_fn, *all_vals)
    node = _TapeNode(_GradOp(), all_inputs,
                     lambda cots: pullback(tuple(cots)),
                     len(out_vals), len(out_vals),
                     out_avals=[(o.shape, o.dtype) for o in out_vals],
                     replay=grad_fn, in_arrays=list(all_vals))
    outs = []
    for i in pos:
        o = NDArray(out_vals[i], uniq[i]._ctx)
        o._tape_node = node
        o._tape_index = i
        outs.append(o)
    return outs


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return grads of heads w.r.t. variables (reference: autograd.py:270).

    With create_graph=True the returned gradients are themselves recorded on
    the tape, so they can be differentiated again (higher-order autograd)."""
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
    if create_graph:
        return _grad_create_graph(heads, variables,
                                  _normalize_head_grads(heads, head_grads))
    if retain_graph is None:
        retain_graph = create_graph
    gs = _walk(heads, _normalize_head_grads(heads, head_grads), retain_graph,
               collect_for=variables)
    out = []
    for v, g in zip(variables, gs):
        if g is None:
            raise MXNetError("autograd.grad: a variable is unreachable "
                             "from the heads")
        out.append(NDArray(g, v._ctx))
    return out


class Function:
    """Custom differentiable function (reference: autograd.py:363).

    Subclass and implement forward(self, *inputs) and
    backward(self, *output_grads), operating on NDArrays with .asjax()."""

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *ograds):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            func = self

            def vjp_fn(cots):
                if not isinstance(cots, tuple):
                    cots = (cots,)
                with pause():
                    grads = func.backward(
                        *[NDArray(c) for c in cots])
                if not isinstance(grads, (list, tuple)):
                    grads = [grads]
                return tuple(g._data if g is not None else None
                             for g in grads)

            class _FakeOp:
                needs_rng = False
                name = "custom_function"
            node = _TapeNode(_FakeOp(), list(inputs), vjp_fn, len(outs),
                             len(outs),
                             out_avals=[(o.shape, o.dtype) for o in outs])
            for i, o in enumerate(outs):
                o._tape_node = node
                o._tape_index = i
        return outs[0] if single else outs
