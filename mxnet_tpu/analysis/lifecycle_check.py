"""mxlint pass 12: ownership & lifecycle discipline (RL12xx).

The engine's design makes every expensive thing a *handle* whose
release is someone else's job — arena pages freed by the scheduler,
sockets evicted by the kvstore client, request futures resolved by the
serve loop, temp dirs removed by the harness that made them.  A handle
that leaks on one early-exit path is invisible in every test that takes
the happy path, and at fleet scale the leak IS the outage.  This pass
tracks acquire/release pairs path-sensitively through each function
body, over the repo's real handle kinds:

==========  =====================================  =======================
kind        acquired by                            released by
==========  =====================================  =======================
arena       ``<x>.alloc(...)``                     ``<x>.free(h, ...)``
socket      ``socket.socket()`` /                  ``h.close()``
            ``socket.create_connection()``
tempfile    ``tempfile.mkdtemp()``                 ``shutil.rmtree(h)``,
                                                   ``os.remove/unlink/
                                                   rmdir(h)``
future      ``Request(...)`` / ``Future()``        ``h.set_result/
                                                   set_exception/
                                                   cancel(...)``
thread      ``threading.Thread(...)``              ``h.join(...)``
            (non-daemon, bound to a local)
==========  =====================================  =======================

Rules:

* **RL1201** (error) — a reachable ``return``/``raise``/fall-through
  exits the function with a handle neither released nor handed off.
* **RL1202** (error) — an OS resource (socket, temp dir) is *used*
  before its cleanup is registered: any statement between the acquire
  and the protecting ``try`` can raise, and the handle leaks.  The fix
  is mechanical — the ``try`` whose ``finally`` (or close-and-reraise
  ``except``) releases the handle must start on the line after the
  acquire.
* **RL1203** (warn) — a Request/Future has a reachable path that
  neither resolves nor cancels it and never hands it off: a waiter on
  that path hangs forever.
* **RL1204** (error) — double release, or any use after release,
  along one path.
* **RL1205** (warn) — a bare/broad ``except: pass`` inside a cleanup
  scope (a ``finally`` block, a try whose body releases something, or
  a close/stop/drain-shaped function): a failed release is silently
  indistinguishable from a successful one.

Like every pass the analysis is conservative: handles are believed
only when literally visible (a direct ``name = <acquire-call>``
binding), handing a handle to any call or storing it anywhere
transfers ownership and ends tracking, and ``with``-managed acquires
are never tracked (the context manager is the cleanup registration).
The dynamic half is ``MXNET_RESCHECK=1`` (``testing/rescheck.py``): a
tracked-handle registry over the same kinds that reports live handles
at ``drain()``/``stop()``/atexit as ``ResourceLeakError`` with
creation stacks — see ``docs/static_analysis.md`` Pass 12.
"""
from __future__ import annotations

import ast
import re

from .findings import Finding

__all__ = ["run"]

# kinds with OS-level cost where an unprotected raise window is itself
# an error (RL1202); the others get leak/double-free tracking only
_OS_KINDS = frozenset({"socket", "tempfile"})

_KIND_NOUN = {
    "arena": "arena pages",
    "socket": "socket",
    "tempfile": "temp file/dir",
    "future": "future",
    "thread": "thread",
}

_FUTURE_CTORS = frozenset({"Request", "Future"})
_TEMPFILE_RELEASERS = frozenset({"rmtree", "remove", "unlink", "rmdir"})
_FUTURE_RESOLVERS = frozenset({"set_result", "set_exception", "cancel"})

_CLEANUP_NAME = re.compile(
    r"(^|_)(close|stop|drain|shutdown|release|free|evict|cleanup|"
    r"uninstall|terminate|teardown|atexit)($|_)|^__(exit|del)__$")

# an `.attr(...)` call whose presence marks a try body as cleanup code
_RELEASE_ATTRS = frozenset({
    "close", "rmtree", "remove", "unlink", "rmdir", "terminate", "kill",
    "shutdown", "cancel", "release", "free", "disarm",
})


def run(path, tree, findings=None):
    """Append RL12xx findings for ``tree`` to ``findings``."""
    findings = findings if findings is not None else []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _FnScan(path, findings).scan(node)
            _scan_swallows(path, node, findings)
    return findings


# ---------------------------------------------------------------------------
# acquire / release vocabulary
# ---------------------------------------------------------------------------
def _acquire_kind(call):
    """Handle kind a ``name = <call>`` binding acquires, or None."""
    f = call.func
    if isinstance(f, ast.Attribute):
        base = f.value
        if isinstance(base, ast.Name):
            if base.id == "socket" and f.attr in ("socket",
                                                  "create_connection"):
                return "socket"
            if base.id == "tempfile" and f.attr == "mkdtemp":
                return "tempfile"
            if base.id == "threading" and f.attr == "Thread":
                return _thread_kind(call)
        if f.attr == "alloc":
            return "arena"
        if f.attr in _FUTURE_CTORS:
            return "future"
    elif isinstance(f, ast.Name):
        if f.id in _FUTURE_CTORS:
            return "future"
        if f.id == "Thread":
            return _thread_kind(call)
    return None


def _thread_kind(call):
    """Daemon threads are fire-and-forget by declaration: untracked."""
    for kw in call.keywords:
        if kw.arg == "daemon" and isinstance(kw.value, ast.Constant) \
                and kw.value.value:
            return None
    return "thread"


def _release_target(call, env):
    """Name of the tracked handle ``call`` releases/resolves, or None."""
    f = call.func
    if not isinstance(f, ast.Attribute):
        return None
    recv = f.value
    if isinstance(recv, ast.Name) and recv.id in env:
        kind = env[recv.id][0].kind
        if f.attr == "close" and kind in ("socket", "tempfile"):
            return recv.id
        if f.attr == "join" and kind == "thread":
            return recv.id
        if f.attr in _FUTURE_RESOLVERS and kind == "future":
            return recv.id
    if isinstance(recv, ast.Name) and recv.id in ("shutil", "os") \
            and f.attr in _TEMPFILE_RELEASERS and call.args:
        a0 = call.args[0]
        if isinstance(a0, ast.Name) and a0.id in env \
                and env[a0.id][0].kind == "tempfile":
            return a0.id
    if f.attr == "free" and call.args:
        a0 = call.args[0]
        if isinstance(a0, ast.Name) and a0.id in env \
                and env[a0.id][0].kind == "arena":
            return a0.id
    return None


def _releases_name(try_node, name):
    """True when ``try_node``'s finally (or any except handler) contains
    a release-shaped call on ``name`` — the handle is *protected*: every
    path out of the try runs the cleanup (finally), or the failure path
    closes and re-raises (the cache-on-success idiom)."""
    blocks = list(try_node.finalbody)
    for h in try_node.handlers:
        blocks.extend(h.body)
    for st in blocks:
        for node in ast.walk(st):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute):
                continue
            f = node.func
            recv = f.value
            if isinstance(recv, ast.Name) and recv.id == name \
                    and f.attr in _RELEASE_ATTRS | _FUTURE_RESOLVERS \
                    | {"join"}:
                return True
            if f.attr in _RELEASE_ATTRS | {"free"} and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id == name:
                return True
    return False


def _none_narrow(test):
    """``(name, branch)`` when ``test`` is a None/falsy check on a bare
    name: ``branch`` is the side on which the name is None/falsy
    (``"body"`` for ``h is None`` / ``not h``, ``"orelse"`` for
    ``h is not None`` / bare ``h``)."""
    if isinstance(test, ast.Compare) and len(test.ops) == 1 \
            and isinstance(test.left, ast.Name) \
            and isinstance(test.comparators[0], ast.Constant) \
            and test.comparators[0].value is None:
        if isinstance(test.ops[0], ast.Is):
            return test.left.id, "body"
        if isinstance(test.ops[0], ast.IsNot):
            return test.left.id, "orelse"
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not) \
            and isinstance(test.operand, ast.Name):
        return test.operand.id, "body"
    if isinstance(test, ast.Name):
        return test.id, "orelse"
    return None, None


# ---------------------------------------------------------------------------
# the path-sensitive walker
# ---------------------------------------------------------------------------
class _Meta:
    """One acquisition site (shared across forked paths for dedupe)."""

    __slots__ = ("name", "kind", "line", "col", "flagged")

    def __init__(self, name, kind, line, col):
        self.name = name
        self.kind = kind
        self.line = line
        self.col = col
        self.flagged = False


# env: {name: [meta, state, release_line]} with state "live"/"released"
_LIVE, _RELEASED = "live", "released"


def _fork(env):
    return {k: list(v) for k, v in env.items()}


def _merge(env, e1, e2):
    env.clear()
    for name in set(e1) | set(e2):
        a, b = e1.get(name), e2.get(name)
        if a is None or b is None:
            env[name] = a or b
        elif a[0] is not b[0]:
            continue  # rebound differently per branch: give up on it
        elif a[1] == _RELEASED and b[1] == _RELEASED:
            env[name] = a
        else:
            # released on one path only: treat as live (optimistic —
            # a later release is legitimate on the live path)
            env[name] = a if a[1] == _LIVE else b


class _FnScan:
    def __init__(self, path, findings):
        self.path = path
        self.findings = findings
        self._try_stack = []

    def _emit(self, line, col, rule, msg):
        self.findings.append(Finding(self.path, line, col, rule, msg))

    def scan(self, fn):
        env = {}
        self.walk(fn.body, env)
        self._exit_check(env, fn.body[-1].lineno if fn.body else fn.lineno,
                         "falls off the end of %s()" % fn.name)

    # -- statements -------------------------------------------------------
    def walk(self, stmts, env):
        for st in stmts:
            self.stmt(st, env)

    def stmt(self, st, env):
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return  # nested defs are scanned as their own functions
        if isinstance(st, ast.Assign):
            self._assign(st, env)
        elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
            if st.value is not None:
                self.value(st.value, env, handoff=True)
        elif isinstance(st, ast.Expr):
            self.value(st.value, env, handoff=False)
        elif isinstance(st, ast.Return):
            if st.value is not None:
                self.value(st.value, env, handoff=True)
            self._exit_check(env, st.lineno, "returns at line %d"
                             % st.lineno)
        elif isinstance(st, ast.Raise):
            if st.exc is not None:
                self.value(st.exc, env, handoff=False)
            self._exit_check(env, st.lineno, "raises at line %d"
                             % st.lineno)
        elif isinstance(st, ast.If):
            self.value(st.test, env, handoff=False)
            e1, e2 = _fork(env), _fork(env)
            # `if h is None:` narrows: the handle was never acquired on
            # that branch (the alloc-returns-None-when-full idiom)
            name, none_branch = _none_narrow(st.test)
            if name is not None:
                (e1 if none_branch == "body" else e2).pop(name, None)
            self.walk(st.body, e1)
            self.walk(st.orelse, e2)
            _merge(env, e1, e2)
        elif isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
            test = st.test if isinstance(st, ast.While) else st.iter
            self.value(test, env, handoff=False)
            self.walk(st.body, env)
            self.walk(st.orelse, env)
        elif isinstance(st, ast.Try):
            self._try(st, env)
        elif isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                # with-managed acquires are never tracked: the context
                # manager IS the cleanup registration
                if not (isinstance(item.context_expr, ast.Call)
                        and _acquire_kind(item.context_expr)):
                    self.value(item.context_expr, env, handoff=False)
            self.walk(st.body, env)
        elif isinstance(st, ast.Delete):
            for t in st.targets:
                if isinstance(t, ast.Name):
                    env.pop(t.id, None)
                else:
                    self.value(t, env, handoff=False)
        elif isinstance(st, ast.Assert):
            self.value(st.test, env, handoff=False)
        elif isinstance(st, (ast.Global, ast.Nonlocal, ast.Pass,
                             ast.Break, ast.Continue, ast.Import,
                             ast.ImportFrom)):
            pass
        # anything else: no handle-relevant semantics

    def _assign(self, st, env):
        value = st.value
        kind = _acquire_kind(value) if isinstance(value, ast.Call) else None
        if kind is not None:
            # still scan the acquire call's own arguments for uses
            for a in value.args:
                self.value(a, env, handoff=True)
            for kw in value.keywords:
                self.value(kw.value, env, handoff=True)
        else:
            self.value(value, env, handoff=True)
        for target in st.targets:
            if isinstance(target, ast.Name):
                old = env.pop(target.id, None)
                if old is not None and old[1] == _LIVE \
                        and not old[0].flagged \
                        and old[0].kind != "future":
                    old[0].flagged = True
                    self._emit(old[0].line, old[0].col, "RL1201",
                               "%s acquired here is dropped by the "
                               "rebinding at line %d without being "
                               "released" % (_KIND_NOUN[old[0].kind],
                                             st.lineno))
            elif isinstance(target, (ast.Tuple, ast.List)):
                for el in target.elts:
                    if isinstance(el, ast.Name):
                        env.pop(el.id, None)
            else:
                self.value(target.value, env, handoff=False) \
                    if isinstance(target, (ast.Attribute, ast.Subscript)) \
                    else None
        if kind is not None and len(st.targets) == 1 \
                and isinstance(st.targets[0], ast.Name):
            name = st.targets[0].id
            if not any(_releases_name(t, name) for t in self._try_stack):
                env[name] = [_Meta(name, kind, st.lineno, st.col_offset),
                             _LIVE, None]

    def _try(self, st, env):
        # entering a try that releases a held handle in its finally (or
        # a close-and-reraise handler) protects it: stop tracking
        for name in [n for n, e in env.items()
                     if e[1] == _LIVE and _releases_name(st, n)]:
            del env[name]
        pre = _fork(env)
        self._try_stack.append(st)
        try:
            self.walk(st.body, env)
            self.walk(st.orelse, env)
            for h in st.handlers:
                # handlers run with the *pre-try* state: the exception
                # may have fired before any acquire in the body
                henv = _fork(pre)
                self.walk(h.body, henv)
        finally:
            self._try_stack.pop()
        self.walk(st.finalbody, env)

    def _exit_check(self, env, line, how):
        for name, entry in list(env.items()):
            meta, state, _rel = entry
            if state != _LIVE or meta.flagged:
                continue
            meta.flagged = True
            if meta.kind == "future":
                self._emit(meta.line, meta.col, "RL1203",
                           "future %r is neither resolved nor cancelled "
                           "on the path that %s — a waiter hangs forever"
                           % (name, how))
            else:
                self._emit(meta.line, meta.col, "RL1201",
                           "%s %r is not released on the path that %s"
                           % (_KIND_NOUN[meta.kind], name, how))

    # -- expressions ------------------------------------------------------
    def value(self, node, env, handoff):
        """Scan an expression: releases, risky uses, escapes, UAR."""
        if node is None:
            return
        if isinstance(node, ast.Call):
            self._call(node, env)
        elif isinstance(node, ast.Name):
            # a bare read (compare, return, container) is never a
            # use-after-release — returning a closed socket or a
            # resolved future is normal; only *operational* uses
            # (call argument / receiver, see _use) flag RL1204
            if handoff and node.id in env:
                del env[node.id]  # ownership handed off: stop tracking
        elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for el in node.elts:
                self.value(el, env, handoff=True)
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                self.value(k, env, handoff=True)
            for v in node.values:
                self.value(v, env, handoff=True)
        elif isinstance(node, (ast.Lambda, ast.GeneratorExp, ast.ListComp,
                               ast.SetComp, ast.DictComp)):
            # closure capture / comprehension use: conservatively an
            # ownership handoff for every tracked name inside
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in env:
                    del env[sub.id]
        elif isinstance(node, (ast.Compare, ast.BoolOp, ast.BinOp,
                               ast.UnaryOp, ast.JoinedStr,
                               ast.FormattedValue, ast.Subscript,
                               ast.Attribute, ast.Starred, ast.Await,
                               ast.IfExp, ast.NamedExpr, ast.Slice)):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.expr, ast.Slice)):
                    self.value(child, env, handoff=False)
        # constants & misc: nothing to do

    def _call(self, call, env):
        released = _release_target(call, env)
        if released is not None:
            entry = env[released]
            # scan the *other* argument expressions too
            for a in call.args:
                if not (isinstance(a, ast.Name) and a.id == released):
                    self.value(a, env, handoff=True)
            if entry[1] == _RELEASED:
                self._emit(call.lineno, call.col_offset, "RL1204",
                           "%s %r released again here — already "
                           "released at line %d"
                           % (_KIND_NOUN[entry[0].kind], released,
                              entry[2]))
                del env[released]
            else:
                entry[1] = _RELEASED
                entry[2] = call.lineno
            return
        # receiver use: h.method(...)
        f = call.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in env:
            self._use(f.value.id, env, call.lineno, call.col_offset,
                      receiver=True)
        else:
            self.value(f, env, handoff=False)
        for a in call.args:
            if isinstance(a, ast.Name) and a.id in env:
                self._use(a.id, env, a.lineno, a.col_offset)
            else:
                self.value(a, env, handoff=True)
        for kw in call.keywords:
            if isinstance(kw.value, ast.Name) and kw.value.id in env:
                self._use(kw.value.id, env, kw.value.lineno,
                          kw.value.col_offset)
            else:
                self.value(kw.value, env, handoff=True)

    def _use(self, name, env, line, col, receiver=False):
        """A tracked handle fed to a non-release call (or used as the
        receiver of one)."""
        entry = env[name]
        meta, state, _rel = entry
        if state == _RELEASED:
            self._uar(name, entry, line, col)
            return
        if meta.kind in _OS_KINDS:
            if not meta.flagged:
                meta.flagged = True
                self._emit(line, col, "RL1202",
                           "%s %r (acquired at line %d) is used before "
                           "its cleanup is registered — an exception "
                           "here leaks it; start the try/finally (or "
                           "close-and-reraise except) on the line after "
                           "the acquire" % (_KIND_NOUN[meta.kind], name,
                                            meta.line))
            del env[name]
        elif receiver:
            # h.method() on a future/thread/page-list is the normal way
            # to operate it (t.start(), fut.done()): keep tracking
            pass
        else:
            # handing an arena page list / future / thread to a call
            # transfers ownership: stop tracking
            del env[name]

    def _uar(self, name, entry, line, col):
        meta = entry[0]
        if not meta.flagged:
            meta.flagged = True
            self._emit(line, col, "RL1204",
                       "%s %r used here after its release at line %d"
                       % (_KIND_NOUN[meta.kind], name, entry[2]))


# ---------------------------------------------------------------------------
# RL1205: broad swallows inside cleanup scopes
# ---------------------------------------------------------------------------
def _broad_handler(handler):
    t = handler.type
    if t is None:
        return True
    names = t.elts if isinstance(t, ast.Tuple) else [t]
    for n in names:
        if isinstance(n, ast.Name) and n.id in ("Exception",
                                                "BaseException"):
            return True
    return False


def _only_pass(body):
    return len(body) == 1 and isinstance(body[0], ast.Pass)


def _has_release_call(stmts):
    for st in stmts:
        for node in ast.walk(st):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _RELEASE_ATTRS:
                return True
    return False


def _scan_swallows(path, fn, findings):
    in_cleanup_fn = bool(_CLEANUP_NAME.search(fn.name))

    def walk(stmts, in_cleanup):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Try):
                scope = in_cleanup or _has_release_call(st.body)
                for h in st.handlers:
                    if scope and _broad_handler(h) and _only_pass(h.body):
                        findings.append(Finding(
                            path, h.lineno, h.col_offset, "RL1205",
                            "broad except swallows failures inside a "
                            "cleanup/release scope — a failed release "
                            "looks successful; catch the narrow OSError "
                            "or record the failure"))
                    walk(h.body, in_cleanup)
                walk(st.body, in_cleanup)
                walk(st.orelse, in_cleanup)
                walk(st.finalbody, True)
            else:
                for attr in ("body", "orelse"):
                    sub = getattr(st, attr, None)
                    if isinstance(sub, list):
                        walk(sub, in_cleanup)

    walk(fn.body, in_cleanup_fn)
