"""Framework-aware AST lint over the paddle_tpu source tree.

The reference institutionalized correctness tooling as scripts + build
wiring (``paddle/scripts`` lint, ASAN in cmake); the TPU-native
equivalents of those bug classes are invisible to generic linters — a
stray ``.item()`` is legal Python, it just costs an on-chip round per
step. Each checker here encodes one hazard class the previous PRs
debugged by hand, with an ID, a fix-it hint, and an inline suppression
syntax:

* **PTA001 host-sync-in-hot-path** — ``.item()``, ``jax.device_get``,
  ``block_until_ready``, or ``float()/int()/np.asarray()`` on a value
  returned by a device step, reachable from a known hot path (trainer
  step loops, serve engine/bundle execution, feeder threads) and NOT
  inside an ``observe_spans.span(...)`` block. Spans are the sanctioned
  materialization points: a sync inside one is measured and deliberate;
  a sync outside one silently serializes the pipeline (PR 6 found
  ~3 ms/step of exactly this).
* **PTA002 jit-cache-buster** — inside a function handed to
  ``jax.jit``/``pjit``/``lax.scan``: Python branching on a traced
  argument (``if x > 0:`` concretizes the tracer — error at best,
  silent retrace-per-value at worst), ``float()/int()/bool()`` on a
  traced argument, f-strings in jit/named_call names (a fresh name per
  call defeats any name-keyed caching or trace grouping), and list/
  dict/set literals passed in ``static_argnums`` positions (unhashable
  — every call re-traces or raises).
* **PTA003 unmanaged-thread** — ``threading.Thread(...)`` without a
  ``name=``. Anonymous threads defeat the thread-leak gate
  (analyze/pytest_plugin.py) and every postmortem; the codebase idiom
  is a named daemon thread with a cancellation handshake
  (data/feeder.py, reader/decorator.py ``_cancellable_put``).
* **PTA004 unlocked-registry** — in a module that uses threading:
  mutation of a module-level container (dict/list/set/WeakSet/...)
  outside a ``with <module-lock>:`` block. Module registries are shared
  by every thread in the process (metrics registry, steplog listener
  set); an unlocked mutation is a data race that only fires under
  serving load.

* **PTA009 span-hygiene** — the request-tracing bug classes
  (docs/observability.md "Request tracing & tail attribution"): a
  ``span(...)`` call that is a bare statement or an assignment (the
  context manager is never entered — the code reads as instrumented
  while timing nothing), and a ``threading.Thread(target=...)`` whose
  target closure-captures a trace context instead of taking it by
  value (``args=`` / a queue item) — closure capture hides the thread
  hop from the trace lane.

PTA005-008 (unguarded shared state, lock-order inversion, naked
condition waits, use-after-donate) are the interprocedural concurrency
and donation checkers — see analyze/concurrency.py; they run through
the same drivers, IDs and suppressions as PTA001-004. PTA006 builds its
lock-acquisition graph across every linted file, so ``lint_paths``/
``lint_tree`` see cross-module cycles a per-file lint cannot.

Suppression: append ``# paddle-lint: disable=PTA001`` (comma-separate
multiple IDs, or ``disable=all``) to the flagged line or the line just
above it. Suppressions are deliberately line-scoped — a file-wide
opt-out would rot.

The checked-in tree lints clean (tests/test_analyze.py pins it); the
fixture tests pin that each checker still fires on its hazard class.
"""

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass

# -- catalog -----------------------------------------------------------------

CHECKERS = {
    "PTA001": ("host-sync-in-hot-path",
               "materialize inside an observe_spans.span(...) block (the "
               "measured, sanctioned sync point) or keep the value "
               "device-resident"),
    "PTA002": ("jit-cache-buster",
               "branch with lax.cond/jnp.where, mark the argument static "
               "(and hashable), and give jit names static strings"),
    "PTA003": ("unmanaged-thread",
               "name the thread and reuse the cancellation idiom "
               "(data/feeder.py: named daemon thread + "
               "reader.decorator._cancellable_put/_drain)"),
    "PTA004": ("unlocked-registry",
               "guard the mutation with the module's lock (add a "
               "module-level threading.Lock() if the module has none)"),
    "PTA005": ("unguarded-shared-state",
               "take the guarding lock around the access (or snapshot "
               "the value under the lock and use the snapshot)"),
    "PTA006": ("lock-order-inversion",
               "acquire the locks in one global order everywhere, or "
               "drop one of them (snapshot under the first lock, call "
               "out after releasing it)"),
    "PTA007": ("naked-condition-wait",
               "wrap the wait in `while <predicate>:` — a woken waiter "
               "must re-test its predicate (see engine._take_batch)"),
    "PTA008": ("use-after-donate",
               "rebind the name from the donating call's results "
               "(x = step(x, ...)) or stop donating the argument"),
    "PTA009": ("span-hygiene",
               "enter spans with `with ...span(...):` (a span call that "
               "is never entered times nothing), and hand trace "
               "contexts to threads as explicit args=/queue items — "
               "closure capture hides the hop from the trace lane"),
}

# Hot-path roots for PTA001, keyed by path suffix. Nested closures
# (e.g. the trainer's per-pass ``finalize``) are scanned as part of
# their enclosing hot function.
HOT_PATHS = {
    "trainer.py": {"_train_passes", "test"},
    "serve/engine.py": {"submit", "_take_batch", "_loop", "_run_batch"},
    "serve/bundle.py": {"run", "infer", "warmup", "decode_step"},
    "serve/scheduler.py": {"submit", "_loop", "_run_iteration",
                           "_distribute", "_plan", "_swap_writer_loop"},
    # the session page file sits on the spill-writer and admission
    # paths: every put/pop/eviction scan runs per swap under load
    "serve/sessions.py": {"put", "pop", "touch", "gone_reason",
                          "_pick_victim_locked", "order"},
    "serve/router.py": {"submit", "total_queued"},
    "serve/fleet.py": {"submit", "queue_depth", "_eligible",
                       "_route_session"},
    # the multi-process data plane's ring + dispatch: put/get run per
    # request per direction inside the busy-poll window, and the
    # router-side submit/rx paths sit on every cross-process request —
    # a host sync here stalls the whole worker fleet
    "serve/workers.py": {"put_frames", "get", "submit", "_submit_to",
                         "_eligible", "_route_session", "_rx_loop",
                         "_dispatch_response", "queue_depth",
                         "_op_traces", "_op_history"},
    # the fleet-of-fleets front: dispatch walks the ring and relays
    # frames per request, and the membership snapshot sits inside that
    # walk — a host sync here stalls every cross-host request
    "serve/cluster.py": {"dispatch_payload", "_candidates", "_snapshot",
                         "_note_landing", "infer"},
    # the remote session store: every spill/restore of every host in
    # the cluster crosses these (client _call, server _dispatch) — the
    # cluster-wide page-file hot path
    "serve/remote_store.py": {"put", "pop", "gone_reason", "_call",
                              "_dispatch"},
    # request-scoped tracing rides every serving submit/retire: the
    # sampler and the exemplar reservoir must never sync with a device
    "observe/tracing.py": {"resolve", "sample", "offer"},
    # the windowed health recorder rides the same submit/retire paths
    # (every request, shed, and dispatch records a window update), and
    # snapshot runs under the recorder's lock — a host sync in any of
    # them stalls the serving hot path fleet-wide
    "observe/health.py": {"record_request", "record_shed",
                          "record_queue_depth", "record_occupancy",
                          "snapshot"},
    # the training-side twin: record_step/record_chunk run inside the
    # trainer's per-step finalize, record_checkpoint on every cadence
    # hit, and snapshot shares their lock — same fleet-wide stall
    # hazard as the serving recorder above
    "observe/trainview.py": {"record_step", "record_chunk",
                             "record_checkpoint", "snapshot"},
    # the elastic driver: its membership-watch handler closure runs at
    # EVERY step boundary (EndIteration), nested inside run_elastic
    "distributed/elastic.py": {"run_elastic"},
    # the quantized-bundle dequant hook is traced INTO every exported
    # program (serve/export.py), so a stray host sync in it would land
    # on every serving dispatch of every quantized bundle
    "serve/quantize.py": {"dequant_for_trace", "dequantize"},
    # materialize: the unpipelined train loop's conversion, on the step
    # thread between BeginIteration and the dispatch
    "data/feeder.py": {"_produce", "batches", "chunks", "materialize"},
    # the async checkpoint writer: submit runs ON the step thread every
    # cadence hit, and the writer loop shares state with it — a stray
    # host sync or an unlocked access here stalls or tears every
    # checkpointing run (PTA003-PTA008 cover the thread/lock idioms)
    "distributed/checkpoint.py": {"submit", "drain", "_writer_loop",
                                  "_write"},
    # per-step dispatch paths that predate PTA001: the cluster worker's
    # whole train loop and the mesh strategy's per-step wrappers
    "distributed/worker.py": {"main"},
    "parallel/mesh.py": {"run", "shard_batch"},
    # the SLO controller's decide/apply cycle runs on the control
    # cadence but its knob apply hooks take the engines' hot-path
    # locks — a host sync while holding one stalls serving exactly
    # when the loop is trying to rescue it
    "control/controller.py": {"step", "_judge_pending_locked",
                              "_decide_locked"},
}

# Calls whose results are device-resident values: reading them back with
# float()/np.asarray() outside a span is the PTA001 hazard.
DEVICE_CALLS = {"_train_step", "_train_chunk", "_eval_step", "call", "run",
                "decode_step"}

# Host-materializing wrappers that flag when applied to a device value.
SYNC_WRAPPERS = {"float", "int", "asarray", "array", "atleast_1d"}

JIT_NAMES = {"jit", "pjit"}
MUTATORS = {"add", "append", "appendleft", "extend", "insert", "remove",
            "discard", "pop", "popleft", "clear", "update", "setdefault"}
CONTAINER_CTORS = {"set", "dict", "list", "deque", "defaultdict",
                   "OrderedDict", "Counter", "WeakSet",
                   "WeakValueDictionary", "WeakKeyDictionary"}
LOCK_CTORS = {"Lock", "RLock", "Condition"}

_SUPPRESS_RE = re.compile(
    r"#\s*paddle-lint:\s*disable=([A-Za-z0-9_,\s]+|all)")


@dataclass
class Finding:
    checker: str
    path: str
    line: int
    message: str

    @property
    def hint(self):
        return CHECKERS[self.checker][1]

    @property
    def title(self):
        return CHECKERS[self.checker][0]

    def as_dict(self):
        """Machine-readable shape of one finding — the ``cli analyze
        --format=json`` record CI annotates PRs from. Key set and
        ordering are a contract (tests/test_analyze.py)."""
        return {"file": self.path, "line": self.line, "id": self.checker,
                "title": self.title, "message": self.message,
                "fixit": self.hint}


def format_finding(f):
    return "%s:%d: %s [%s %s]\n    fix: %s" % (
        f.path, f.line, f.message, f.checker, f.title, f.hint)


# -- suppression -------------------------------------------------------------

def _suppressions(source):
    """{line_number: set of suppressed checker ids (or {"all"})} from
    ``# paddle-lint: disable=...`` comments."""
    out = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            ids = {s.strip() for s in m.group(1).split(",") if s.strip()}
            out.setdefault(tok.start[0], set()).update(
                {"all"} if "all" in ids else ids)
    except tokenize.TokenError:
        pass
    return out


def _suppressed(finding, suppressions):
    for line in (finding.line, finding.line - 1):
        ids = suppressions.get(line)
        if ids and ("all" in ids or finding.checker in ids):
            return True
    return False


# -- shared AST helpers ------------------------------------------------------

def _call_name(func):
    """Trailing identifier of a call target: Name or Attribute."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _names_in(node):
    """All Name identifiers in a subtree — used both for reads (span
    lock contexts, sync-wrapper arguments) and for assignment targets
    (tuple/list unpack and starred targets fall out of ast.walk)."""
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_span_with(node):
    """True for ``with ...span(...):`` — the sanctioned sync scope."""
    for item in node.items:
        expr = item.context_expr
        if isinstance(expr, ast.Call) and _call_name(expr.func) == "span":
            return True
    return False


# -- PTA001: host sync in hot path -------------------------------------------

class _HotPathChecker(ast.NodeVisitor):
    def __init__(self, path, findings):
        self.path = path
        self.findings = findings
        self.tracked = set()
        self.span_depth = 0

    def run(self, func_node):
        self._collect_tracked(func_node)
        for stmt in func_node.body:
            self.visit(stmt)

    def _collect_tracked(self, func_node):
        """Names bound (directly or via iteration) to device-step
        results. Two passes so iteration taint over a tracked name
        (``for k, v in out.items():``) resolves."""
        for _ in range(2):
            for node in ast.walk(func_node):
                if isinstance(node, ast.Assign):
                    if self._is_device_call(node.value):
                        for t in node.targets:
                            self.tracked |= _names_in(t)
                elif isinstance(node, (ast.For, ast.comprehension)):
                    it = node.iter
                    if _names_in(it) & self.tracked:
                        self.tracked |= _names_in(node.target)

    def _is_device_call(self, value):
        return (isinstance(value, ast.Call)
                and _call_name(value.func) in DEVICE_CALLS)

    def visit_With(self, node):
        if _is_span_with(node):
            self.span_depth += 1
            for stmt in node.body:
                self.visit(stmt)
            self.span_depth -= 1
        else:
            self.generic_visit(node)

    def visit_Call(self, node):
        if self.span_depth == 0:
            name = _call_name(node.func)
            if name == "item" and isinstance(node.func, ast.Attribute) \
                    and not node.args:
                self._flag(node, ".item() forces a device round-trip")
            elif name in ("device_get", "block_until_ready"):
                self._flag(node, "%s() synchronizes with the device"
                           % name)
            elif name in SYNC_WRAPPERS and node.args:
                hit = _names_in(node.args[0]) & self.tracked
                if hit:
                    self._flag(node, "%s() on device value %r reads it "
                               "back to the host" % (name, sorted(hit)[0]))
        self.generic_visit(node)

    def _flag(self, node, what):
        self.findings.append(Finding(
            "PTA001", self.path, node.lineno,
            "%s on a hot path, outside any observe span" % what))


def _check_hot_paths(tree, path, findings):
    norm = path.replace(os.sep, "/")
    hot = None
    for suffix, names in HOT_PATHS.items():
        if norm.endswith(suffix):
            hot = names
            break
    if hot is None:
        return
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in hot:
            _HotPathChecker(path, findings).run(node)


# -- PTA002: jit cache busters -----------------------------------------------

def _jit_call(node):
    """The jit-family call inside ``node``, unwrapping partial(...)."""
    if not isinstance(node, ast.Call):
        return None
    name = _call_name(node.func)
    if name in JIT_NAMES:
        return node
    if name == "partial" and node.args:
        if _call_name(node.args[0]) in JIT_NAMES:
            return node
    return None


def _collect_jitted(tree):
    """[(FunctionDef, jit Call-or-None)] for every function that is
    jitted by decorator, wrapped by a jit/pjit call, or used as a
    lax.scan body."""
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)
    jitted = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for deco in node.decorator_list:
                if _call_name(deco) in JIT_NAMES or _jit_call(deco):
                    jitted.append((node, deco if isinstance(deco, ast.Call)
                                   else None))
        elif isinstance(node, ast.Call):
            name = _call_name(node.func)
            if name in JIT_NAMES and node.args \
                    and isinstance(node.args[0], ast.Name):
                for fn in defs.get(node.args[0].id, ()):
                    jitted.append((fn, node))
            elif name == "scan" and node.args \
                    and isinstance(node.args[0], ast.Name):
                for fn in defs.get(node.args[0].id, ()):
                    jitted.append((fn, None))
    return jitted


def _traced_params(func_node, jit_call):
    """Argument names traced by jit (static_argnums/argnames excluded)."""
    a = func_node.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    static = set()
    if jit_call is not None:
        for kw in jit_call.keywords:
            val = kw.value
            if kw.arg == "static_argnums":
                for c in ast.walk(val):
                    if isinstance(c, ast.Constant) and isinstance(c.value,
                                                                  int):
                        if 0 <= c.value < len(names):
                            static.add(names[c.value])
            elif kw.arg == "static_argnames":
                for c in ast.walk(val):
                    if isinstance(c, ast.Constant) and isinstance(c.value,
                                                                  str):
                        static.add(c.value)
    return {n for n in names if n not in static and n != "self"}


def _tracer_in_test(test, params):
    """A traced param used as a Python truth value in ``test`` (None
    checks, isinstance/len calls and attribute access are static and
    exempt). Returns the offending name or None."""
    if isinstance(test, ast.Name):
        return test.id if test.id in params else None
    if isinstance(test, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return None
        for operand in [test.left] + list(test.comparators):
            if isinstance(operand, ast.Name) and operand.id in params:
                return operand.id
        return None
    if isinstance(test, ast.BoolOp):
        for v in test.values:
            hit = _tracer_in_test(v, params)
            if hit:
                return hit
        return None
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _tracer_in_test(test.operand, params)
    return None


def _check_jit_bodies(tree, path, findings):
    seen = set()
    for func_node, jit_call in _collect_jitted(tree):
        key = (func_node.lineno, func_node.name)
        if key in seen:
            continue
        seen.add(key)
        params = _traced_params(func_node, jit_call)
        for node in ast.walk(func_node):
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                hit = _tracer_in_test(node.test, params)
                if hit:
                    findings.append(Finding(
                        "PTA002", path, node.lineno,
                        "Python branch on traced argument %r inside "
                        "jitted %r — concretizes the tracer (or retraces "
                        "per value)" % (hit, func_node.name)))
            elif isinstance(node, ast.Call):
                name = _call_name(node.func)
                if name in ("float", "int", "bool") and node.args \
                        and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in params:
                    findings.append(Finding(
                        "PTA002", path, node.lineno,
                        "%s() on traced argument %r inside jitted %r "
                        "forces concretization" % (name, node.args[0].id,
                                                   func_node.name)))


def _check_jit_callsites(tree, path, findings):
    """f-strings in jit/named_call names; non-hashable literals passed
    at static_argnums positions of a module-local jitted callable."""
    static_of = {}  # assigned name -> sorted static argnums
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name in JIT_NAMES | {"named_call", "named_scope"}:
            fstr = [a for a in list(node.args)
                    + [k.value for k in node.keywords]
                    if isinstance(a, ast.JoinedStr)]
            if fstr:
                findings.append(Finding(
                    "PTA002", path, fstr[0].lineno,
                    "f-string in %s name — a fresh name per call defeats "
                    "name-keyed caching/trace grouping" % name))
        if name in JIT_NAMES:
            nums = []
            for kw in node.keywords:
                if kw.arg == "static_argnums":
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) \
                                and isinstance(c.value, int):
                            nums.append(c.value)
            if nums:
                parent = getattr(node, "_pl_parent", None)
                if isinstance(parent, ast.Assign) \
                        and len(parent.targets) == 1 \
                        and isinstance(parent.targets[0], ast.Name):
                    static_of[parent.targets[0].id] = sorted(nums)
    if not static_of:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in static_of:
            for pos in static_of[node.func.id]:
                if pos < len(node.args) and isinstance(
                        node.args[pos],
                        (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
                    findings.append(Finding(
                        "PTA002", path, node.args[pos].lineno,
                        "non-hashable literal passed at static_argnums "
                        "position %d of %r — jit static args must hash "
                        "(use a tuple)" % (pos, node.func.id)))


# -- PTA003: unmanaged threads -----------------------------------------------

def _check_threads(tree, path, findings):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_name(node.func) != "Thread":
            continue
        kwargs = {kw.arg for kw in node.keywords}
        if "name" not in kwargs:
            findings.append(Finding(
                "PTA003", path, node.lineno,
                "threading.Thread(...) without name= — anonymous threads "
                "are invisible to the leak gate and postmortems"))


# -- PTA004: unlocked module registries --------------------------------------

def _module_imports_threading(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "threading" for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "threading":
                return True
    return False


def _module_registries(tree):
    """(container_names, lock_names) bound at module top level."""
    containers, locks = set(), set()
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        names = set()
        for t in node.targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
        if not names:
            continue
        value = node.value
        if isinstance(value, (ast.Dict, ast.List, ast.Set)):
            containers |= names
        elif isinstance(value, ast.Call):
            ctor = _call_name(value.func)
            if ctor in CONTAINER_CTORS:
                containers |= names
            elif ctor in LOCK_CTORS:
                locks |= names
    return containers, locks


class _RegistryChecker(ast.NodeVisitor):
    def __init__(self, path, containers, locks, findings):
        self.path = path
        self.containers = containers
        self.locks = locks
        self.findings = findings
        self.lock_depth = 0
        self.fn_depth = 0

    def visit_With(self, node):
        locked = any(_names_in(item.context_expr) & self.locks
                     for item in node.items)
        if locked:
            self.lock_depth += 1
        self.generic_visit(node)
        if locked:
            self.lock_depth -= 1

    def _visit_fn(self, node):
        self.fn_depth += 1
        self.generic_visit(node)
        self.fn_depth -= 1

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _flag(self, node, name, how):
        if self.fn_depth == 0:
            return  # import-time mutation: single-threaded by definition
        if self.lock_depth > 0:
            return
        extra = (" (module locks: %s)" % ", ".join(sorted(self.locks))
                 if self.locks else " (module defines no lock)")
        self.findings.append(Finding(
            "PTA004", self.path, node.lineno,
            "module-level registry %r mutated via %s outside its lock%s"
            % (name, how, extra)))

    def visit_Call(self, node):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in MUTATORS \
                and isinstance(func.value, ast.Name) \
                and func.value.id in self.containers:
            self._flag(node, func.value.id, ".%s()" % func.attr)
        self.generic_visit(node)

    def _sub_target(self, target):
        if isinstance(target, ast.Subscript) \
                and isinstance(target.value, ast.Name) \
                and target.value.id in self.containers:
            return target.value.id
        return None

    def visit_Assign(self, node):
        for t in node.targets:
            name = self._sub_target(t)
            if name:
                self._flag(node, name, "item assignment")
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        name = self._sub_target(node.target)
        if name:
            self._flag(node, name, "augmented item assignment")
        self.generic_visit(node)

    def visit_Delete(self, node):
        for t in node.targets:
            name = self._sub_target(t)
            if name:
                self._flag(node, name, "item deletion")
        self.generic_visit(node)


def _check_registries(tree, path, findings):
    if not _module_imports_threading(tree):
        return
    containers, locks = _module_registries(tree)
    if not containers:
        return
    _RegistryChecker(path, containers, locks, findings).visit(tree)


# -- PTA009: span hygiene & trace-context thread handoff ----------------------

# calls that produce a TraceContext (observe/tracing.py): unqualified
# constructor-ish names plus the module-qualified sampler entry points
TRACE_CTX_ATTRS = {"mint", "from_traceparent", "child"}
TRACE_CTX_MODULES = {"tracing", "observe_tracing"}
# parameter names that ARE a trace context by convention (the serving
# tier's submit(..., trace=...) signatures)
TRACE_NAME_HINTS = {"trace", "trace_ctx", "trace_context", "tracectx"}


def _is_trace_ctx_value(value):
    if not isinstance(value, ast.Call):
        return False
    func = value.func
    if _call_name(func) in TRACE_CTX_ATTRS:
        return True
    return (isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in TRACE_CTX_MODULES
            and func.attr in {"resolve", "sample"})


def _bound_names(fn):
    """Names bound inside a function body (params, assignments, for
    targets, with-as, comprehension targets) — the complement of its
    free variables."""
    a = fn.args
    bound = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    if getattr(a, "vararg", None):
        bound.add(a.vararg.arg)
    if getattr(a, "kwarg", None):
        bound.add(a.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                bound |= _names_in(t)
        elif isinstance(node, (ast.For, ast.comprehension)):
            bound |= _names_in(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            bound |= _names_in(node.optional_vars)
    return bound


def _free_reads(fn):
    """Names read inside ``fn`` that it does not bind itself — its
    closure captures."""
    reads = {n.id for n in ast.walk(fn)
             if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
    return reads - _bound_names(fn)


class _SpanHygieneChecker:
    """PTA009 both halves: (a) a ``span(...)`` call that is a bare
    statement or an assignment target is a context manager that is
    NEVER ENTERED — it times nothing while reading as if it did;
    (b) a ``threading.Thread(target=inner)`` whose inner function
    closure-captures a trace context from the enclosing scope hides a
    thread hop from the trace lane — contexts must cross threads as
    explicit ``args=`` (or ride the queue item), the by-value rule the
    whole serving tier follows (engine request objects, the
    scheduler's swap-queue tuples)."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings

    def check_spans(self, tree):
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node.func) == "span"
                    and (node.args or node.keywords)):
                continue
            parent = getattr(node, "_pl_parent", None)
            if isinstance(parent, ast.Expr):
                self.findings.append(Finding(
                    "PTA009", self.path, node.lineno,
                    "span(...) as a bare statement — the context "
                    "manager is never entered, so nothing is timed"))
            elif isinstance(parent, (ast.Assign, ast.AugAssign)):
                self.findings.append(Finding(
                    "PTA009", self.path, node.lineno,
                    "span(...) assigned instead of entered — use "
                    "`with ...span(...) as scope:`"))

    def check_thread_handoff(self, tree):
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            trace_names = {p for p in _bound_names(fn)
                           if p in TRACE_NAME_HINTS}
            local_defs = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.FunctionDef) and node is not fn:
                    local_defs.setdefault(node.name, node)
                elif isinstance(node, ast.Assign) \
                        and _is_trace_ctx_value(node.value):
                    for t in node.targets:
                        trace_names |= _names_in(t)
                elif isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Lambda) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    local_defs[node.targets[0].id] = node.value
            if not trace_names:
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and _call_name(node.func) == "Thread"):
                    continue
                target = next((kw.value for kw in node.keywords
                               if kw.arg == "target"), None)
                inner = None
                if isinstance(target, ast.Lambda):
                    inner = target
                elif isinstance(target, ast.Name):
                    inner = local_defs.get(target.id)
                if inner is None:
                    continue
                explicit = set()
                for kw in node.keywords:
                    if kw.arg in ("args", "kwargs"):
                        explicit |= _names_in(kw.value)
                captured = (_free_reads(inner) & trace_names) - explicit
                for name in sorted(captured):
                    self.findings.append(Finding(
                        "PTA009", self.path, node.lineno,
                        "trace context %r captured into a thread via "
                        "closure — pass it by value (Thread args= or a "
                        "queue item) so the hop stays explicit" % name))


def _check_span_hygiene(tree, path, findings):
    checker = _SpanHygieneChecker(path, findings)
    checker.check_spans(tree)
    checker.check_thread_handoff(tree)


# -- driver ------------------------------------------------------------------

def _annotate_parents(tree):
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._pl_parent = node


def _lint_file(source, path):
    """Per-file checks (PTA001-005, 007, 008). Returns (kept findings,
    concurrency file model for the cross-file lock graph, suppressions)."""
    from paddle_tpu.analyze import concurrency

    tree = ast.parse(source, filename=path)
    _annotate_parents(tree)
    findings = []
    _check_hot_paths(tree, path, findings)
    _check_jit_bodies(tree, path, findings)
    _check_jit_callsites(tree, path, findings)
    _check_threads(tree, path, findings)
    _check_registries(tree, path, findings)
    _check_span_hygiene(tree, path, findings)
    model = concurrency.collect_file_model(tree, path)
    concurrency.check_file(tree, model, findings)
    suppressions = _suppressions(source)
    kept = [f for f in findings if not _suppressed(f, suppressions)]
    return kept, model, suppressions


def lint_source(source, path="<string>"):
    """Lint one source string; returns unsuppressed [Finding]. The
    PTA006 lock graph covers only this file here — multi-file cycles
    need :func:`lint_paths`/:func:`lint_tree`."""
    from paddle_tpu.analyze import concurrency

    kept, model, suppressions = _lint_file(source, path)
    graph = []
    concurrency.check_lock_graph([model], graph)
    kept += [f for f in graph if not _suppressed(f, suppressions)]
    kept.sort(key=lambda f: (f.path, f.line, f.checker))
    return kept


def lint_paths(paths):
    """Lint several files, running the PTA006 lock-acquisition graph
    over all of them at once (cross-module cycles)."""
    from paddle_tpu.analyze import concurrency

    findings = []
    models = []
    suppressions_of = {}
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            kept, model, suppressions = _lint_file(fh.read(), path)
        findings.extend(kept)
        models.append(model)
        suppressions_of[path] = suppressions
    graph = []
    concurrency.check_lock_graph(models, graph)
    findings += [f for f in graph
                 if not _suppressed(f, suppressions_of.get(f.path, {}))]
    findings.sort(key=lambda f: (f.path, f.line, f.checker))
    return findings


def lint_tree(root=None):
    """Lint every .py under ``root`` (default: the installed paddle_tpu
    package). Returns (findings, files_checked)."""
    if root is None:
        import paddle_tpu

        root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
    paths = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        paths.extend(os.path.join(dirpath, f)
                     for f in sorted(filenames) if f.endswith(".py"))
    return lint_paths(sorted(paths)), len(paths)
