"""Imperative autograd: tape + reverse pass.

Reference: ``python/mxnet/autograd.py`` + ``src/imperative/imperative.cc``
(``RecordOp:193`` builds grad-graph nodes; ``Backward:280`` runs the nnvm
``Gradient`` pass then executes the backward graph).

TPU-native design: instead of stashing ``AGInfo`` on nnvm nodes and re-deriving
a backward graph per op via ``FGradient``, every recorded op captures its XLA
VJP closure at invoke time (``jax.vjp`` over the op's jitted forward).  The
backward pass is then a pure tape walk — reverse topological order, calling
each node's VJP and accumulating cotangents.  Residuals live in device memory
as XLA buffers; recomputation/checkpointing is handled at the graph (hybridize)
level instead.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError

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
    # NOT a bulk-segment boundary: ops recorded under the tape defer into
    # segments like any other op (their TapeNode primals hold _BulkRefs
    # that resolve — flushing on demand — at backward time), so a whole
    # recorded forward fuses without the tape ever forcing a flush.
    st = _st()
    prev, st.recording = st.recording, bool(is_record)
    return prev


def set_training(train_mode):
    st = _st()
    prev, st.training = st.training, bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._rec = is_record
        self._train = train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._rec is not None:
            st.recording = self._rec
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *args):
        st = _st()
        st.recording, st.training = self._prev


def record(train_mode=True):
    """Scope in which ops on marked arrays are taped (parity: autograd.record:122)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


class TapeNode:
    """One recorded op: VJP closure + graph edges.

    ``inputs`` are the NDArray objects fed to the op (leaf or intermediate),
    ``out_avals`` the (shape, dtype) of each op output so missing head
    gradients can be zero-filled, ``skip_grad_inputs`` marks leading non-
    differentiable args (e.g. RNG keys) whose cotangents are discarded.
    """

    __slots__ = (
        "vjp_fn",
        "inputs",
        "out_avals",
        "skip_grad_inputs",
        "cotangents",
        "op_name",
        "prim",
        "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, skip_grad_inputs=0, op_name="",
                 prim=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs
        self.out_avals = out_avals
        self.skip_grad_inputs = skip_grad_inputs
        self.cotangents = None
        self.op_name = op_name
        # (fn, datas, n_rng): the primal callable + raw input arrays, kept so
        # create_graph=True can RE-linearize (jax.vjp closures bake the primal
        # point in, so higher order needs the function itself; reference:
        # second-order FGradient entries like _backward_backward_FullyConnected,
        # src/operator/nn/fully_connected.cc:363)
        self.prim = prim

    def seed(self, idx, ct):
        if self.cotangents is None:
            self.cotangents = [None] * len(self.out_avals)
        dtype = self.out_avals[idx][1]
        if getattr(ct, "dtype", None) != dtype:
            # consumers may run in a different dtype than this op produced
            # (AMP dispatch-time casts); vjp demands exact cotangent dtypes
            ct = ct.astype(dtype)
        cur = self.cotangents[idx]
        self.cotangents[idx] = ct if cur is None else cur + ct

    def materialize_cotangents(self):
        if self.cotangents is None:
            self.cotangents = [None] * len(self.out_avals)
        outs = []
        for ct, (shape, dtype) in zip(self.cotangents, self.out_avals):
            if ct is None:
                # an integer output (a router's indices) has no tangent
                # space: jax.vjp takes a float0 cotangent for it
                ct = jnp.zeros(shape, dtype) \
                    if jnp.issubdtype(dtype, jnp.inexact) \
                    else _np.zeros(shape, jax.dtypes.float0)
            outs.append(ct)
        return tuple(outs)


def _topo_order(root_nodes):
    """Reverse-topological (output→input) order over reachable tape nodes."""
    order = []
    seen = set()
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for inp in node.inputs:
            child = inp._tape_node
            if child is not None and id(child) not in seen:
                stack.append((child, False))
    # order is inputs-before-outputs; backward wants outputs first
    order.reverse()
    return order


_backward_gen = [0]


def current_backward_gen():
    return _backward_gen[0]


def _resolve_prim_datas(datas):
    """Materialize any ``_BulkRef`` primals recorded through a segment.

    A TapeNode recorded while its op was deferred holds segment promises
    instead of concrete buffers; the first backward that needs one
    flushes its segment (one fused push) and reads the landed value —
    the tape itself never forces a flush at record time.
    """
    from .engine import _BulkRef

    if not any(type(d) is _BulkRef for d in datas):
        return datas
    out = []
    for d in datas:
        if type(d) is _BulkRef:
            if d.value is None and not d.failed:
                d.segment.flush("backward")
            if d.value is None:
                raise MXNetError(
                    "cannot run backward: a deferred forward value was "
                    "lost (its bulk segment failed)")
            out.append(d.value)
        else:
            out.append(d)
    return tuple(out)


def _node_backward(node, cts):
    """Run one node's backward.

    Nodes recorded by the lazy tape carry only their primal
    (``node.prim``); the vjp runs as ONE cached jitted executable per
    stable op callable (``fn._mx_bwd``), so neither recording nor
    backward re-traces ``jax.vjp`` per invocation — the tape-walk
    analogue of the reference executing a prebuilt backward graph.
    Ad-hoc closures (invoke_fn, control flow) linearize eagerly.
    """
    import jax

    if node.vjp_fn is not None:
        return node.vjp_fn(cts)
    fn, datas, _n_rng = node.prim
    datas = _resolve_prim_datas(datas)
    bwd = getattr(fn, "_mx_bwd", None)
    if bwd is None:
        def bwd_fn(primals, cotangents):
            _, vjp = jax.vjp(fn, *primals)
            return vjp(cotangents)

        if getattr(fn, "_mx_stable", False):
            bwd = jax.jit(bwd_fn)
            try:
                fn._mx_bwd = bwd
            except Exception:  # wrapper types that reject attributes
                pass
        else:
            bwd = bwd_fn
    return bwd(tuple(datas), tuple(cts))


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run the reverse pass from ``heads`` (parity: MXAutogradBackwardEx).

    Gradients accumulate into ``.grad`` of every reachable leaf that called
    ``attach_grad``.  ``train_mode`` is accepted for parity; the mode was
    already baked into the taped VJPs at record time (XLA closures are
    specialized, so there is no late mode switch — documented deviation).
    """
    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    if len(head_grads) != len(heads):
        raise MXNetError("len(head_grads) != len(heads)")
    _backward_gen[0] += 1

    roots = []
    for h, hg in zip(heads, head_grads):
        node = h._tape_node
        if node is None:
            if h._marked:
                # backward on a bare leaf: grad = head_grad (ones by default)
                g = hg.data() if hasattr(hg, "data") else (
                    jnp.ones(h.shape, h.dtype) if hg is None else jnp.asarray(hg)
                )
                h._accumulate_grad(g)
                continue
            raise MXNetError(
                "cannot differentiate a head that is not in the recorded graph"
            )
        g = (
            jnp.ones(h.shape, h.dtype)
            if hg is None
            else (hg.data() if hasattr(hg, "data") else jnp.asarray(hg))
        )
        node.seed(h._tape_index, g)
        roots.append(node)

    for node in _topo_order(roots):
        if node.cotangents is None:
            continue  # not on a path from any head
        if node.vjp_fn is None and node.prim is None:
            raise MXNetError(
                "graph already freed by a previous backward; "
                "pass retain_graph=True to backward() to reuse it"
            )
        cts = node.materialize_cotangents()
        # consume the seeds NOW: a later backward over a retained graph
        # must start from fresh cotangents, not accumulate onto these
        node.cotangents = None
        in_cts = _node_backward(node, cts)
        if not retain_graph:
            node.vjp_fn = None
            node.prim = None
        skip = node.skip_grad_inputs
        for inp, ct in zip(node.inputs, in_cts[skip:] if skip else in_cts):
            if ct is None or getattr(ct, "dtype", None) == jax.dtypes.float0:
                continue        # float0: the cotangent of an integer input
            child = inp._tape_node
            if child is not None:
                if hasattr(ct, "tostype"):  # sparse ct into an interior node
                    ct = ct.tostype("default").data()
                child.seed(inp._tape_index, ct)
            elif inp._marked:
                inp._accumulate_grad(ct)

    # seeds were consumed node-by-node in the loop; nothing left to clear


def _apply_node_vjp_taped(node, cts):
    """Apply a node's backward as a RECORDED op (create_graph support).

    ``cts`` are NDArray cotangents for each node output.  Re-linearizes the
    stored primal (``node.prim``) so the produced input-cotangents carry
    their own tape nodes — grads of grads (and third order, recursively)
    just work.  Returns NDArray-or-None per ``node.inputs`` entry.
    """
    import jax

    from .ndarray.ndarray import NDArray

    raw_cts = tuple(c.data() for c in cts)
    if node.prim is None:
        # opaque vjp (custom Function, hybridized cache): first-order only
        raw = node.vjp_fn(raw_cts)
        skip = node.skip_grad_inputs
        raw = raw[skip:] if skip else raw
        return [None if g is None else NDArray(g) for g in raw]

    fn, datas, n_rng = node.prim
    datas = _resolve_prim_datas(datas)
    n_prim = len(datas)

    def full(*args):
        prim, ct = args[:n_prim], args[n_prim:]
        _, vjp = jax.vjp(fn, *prim)
        return vjp(tuple(ct))

    args = tuple(datas) + raw_cts
    outs, vjp2 = jax.vjp(full, *args)
    new_node = TapeNode(
        vjp2,
        list(node.inputs) + list(cts),
        [(o.shape, o.dtype) for o in outs],
        skip_grad_inputs=n_rng,
        op_name="_backward_" + node.op_name,
        prim=(full, args, n_rng),
    )
    results = []
    for i in range(n_rng, n_prim):
        arr = NDArray(outs[i])
        arr._tape_node = new_node
        arr._tape_index = i
        results.append(arr)
    return results


def _taped_backward(heads, head_grads, train_mode=True):
    """NDArray-valued reverse pass that records itself (create_graph=True).

    Returns ``{id(leaf NDArray): grad NDArray}`` for every reachable marked
    leaf; grad NDArrays carry tape nodes, so a second ``backward``/``grad``
    differentiates through them.
    """
    import jax.numpy as jnp

    from .ndarray.ndarray import NDArray

    seeds = {}
    node_by_id = {}

    def seed_nd(node, idx, ct):
        node_by_id[id(node)] = node
        lst = seeds.setdefault(id(node), [None] * len(node.out_avals))
        lst[idx] = ct if lst[idx] is None else lst[idx] + ct

    leaf_grads = {}

    def leaf_nd(leaf, ct):
        cur = leaf_grads.get(id(leaf))
        leaf_grads[id(leaf)] = ct if cur is None else cur + ct

    roots = []
    with record(train_mode):
        for h, hg in zip(heads, head_grads):
            g = hg if isinstance(hg, NDArray) else NDArray(
                jnp.ones(h.shape, h.dtype) if hg is None
                else jnp.asarray(hg))
            node = h._tape_node
            if node is None:
                if h._marked:
                    leaf_nd(h, g)
                    continue
                raise MXNetError(
                    "cannot differentiate a head that is not in the "
                    "recorded graph")
            seed_nd(node, h._tape_index, g)
            roots.append(node)

        for node in _topo_order(roots):
            lst = seeds.get(id(node))
            if lst is None:
                continue
            cts = [c if c is not None else NDArray(jnp.zeros(s, d))
                   for c, (s, d) in zip(lst, node.out_avals)]
            in_cts = _apply_node_vjp_taped(node, cts)
            for inp, ct in zip(node.inputs, in_cts):
                if ct is None:
                    continue
                child = inp._tape_node
                if child is not None:
                    seed_nd(child, inp._tape_index, ct)
                elif inp._marked:
                    leaf_nd(inp, ct)
    return leaf_grads


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Return grads of ``heads`` w.r.t. ``variables`` without touching ``.grad``.

    Parity: ``autograd.grad`` (python/mxnet/autograd.py:273).  With
    ``create_graph=True`` the backward pass itself is recorded (each node's
    primal is re-linearized via ``jax.vjp``), so the returned grads can be
    differentiated again — arbitrary order (ref test_higher_order_grad.py).
    """
    from .ndarray.ndarray import NDArray

    if create_graph:
        single = isinstance(variables, NDArray)
        var_list = [variables] if single else list(variables)
        if isinstance(heads, NDArray):
            heads = [heads]
            if head_grads is not None and not isinstance(
                    head_grads, (list, tuple)):
                head_grads = [head_grads]
        if head_grads is None:
            head_grads = [None] * len(heads)
        saved = [v._marked for v in var_list]
        for v in var_list:
            v._marked = True
        try:
            leaf_map = _taped_backward(heads, head_grads, train_mode)
        finally:
            for v, m in zip(var_list, saved):
                v._marked = m
        outs = []
        for v in var_list:
            g = leaf_map.get(id(v))
            if g is None:
                import jax.numpy as jnp

                g = NDArray(jnp.zeros(v.shape, v.dtype), ctx=v.context)
            outs.append(g)
        return outs[0] if single else outs
    single = isinstance(variables, NDArray)
    if single:
        variables = [variables]
    saved = [(v._grad, v._grad_req) for v in variables]
    for v in variables:
        v._grad = None
        v._grad_req = "add"
        v._marked = True
    try:
        backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
        outs = []
        for v in variables:
            if v._grad is None:
                outs.append(NDArray(jnp.zeros(v.shape, v.dtype), ctx=v.context))
            else:
                outs.append(NDArray(v._grad, ctx=v.context))
    finally:
        for v, (g, req) in zip(variables, saved):
            v._grad, v._grad_req = g, req
    return outs[0] if single else outs


class Function:
    """Custom-gradient block (parity: autograd.Function, autograd.py:370)."""

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):  # pragma: no cover - abstract
        raise NotImplementedError

    def backward(self, *output_grads):  # pragma: no cover - abstract
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, array

        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)
        if is_recording() and any(
            isinstance(i, NDArray) and i._in_graph for i in inputs
        ):
            nd_inputs = [i for i in inputs if isinstance(i, NDArray)]

            def vjp_fn(cts):
                with pause():
                    igrads = self.backward(*[NDArray(c) for c in cts])
                if isinstance(igrads, NDArray):
                    igrads = [igrads]
                return tuple(
                    g.data() if isinstance(g, NDArray) else g for g in igrads
                )

            node = TapeNode(
                vjp_fn,
                nd_inputs,
                [(o.shape, o.dtype) for o in outs],
                op_name=type(self).__name__,
            )
            for i, o in enumerate(outs):
                o._tape_node = node
                o._tape_index = i
        return outputs


def mark_variables(variables, gradients, grad_reqs="write"):
    """Parity: autograd.mark_variables / Imperative::MarkVariables."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._marked = True
        v._grad = g.data() if hasattr(g, "data") else g
        v._grad_req = req
