"""savlint rules: the TPU/JAX failure modes worth failing CI over.

Every rule carries an ID (stable — pragmas and the baseline key on it),
a severity, a one-line fix-it hint, and a docstring that is the
catalogue entry rendered into docs/static_analysis.md. The common theme:
each rule encodes a discipline the runtime already depends on (PR 1's
retrace counter, PR 2's feeder threading contract) but that nothing
enforced statically — so a future edit could silently regress a
multi-hour TPU run. Rules are heuristics, not proofs: the pragma and
baseline escapes exist precisely because ``evaluate()``'s one
end-of-pass ``device_get`` is correct and ``bench.py``'s sync-per-step
is the point. The bar for a rule is "a finding is worth a human reading
the line", not zero false positives.

Adding a rule (docs/static_analysis.md has the full recipe): subclass
:class:`Rule`, pick the next SAV1xx id, implement ``check(module)``
yielding :class:`~sav_tpu.analysis.lint.Finding`, append to
``ALL_RULES``, add a known-bad + known-clean fixture pair under
tests/analysis_fixtures/ and an entry in tests/test_savlint_rules.py.
"""

from __future__ import annotations

import ast
from typing import Iterator

from sav_tpu.analysis.lint import Finding, ModuleInfo, _bare_name

# Functions forming the training hot path: syncs here serialize the
# device pipeline every step (or every eval batch). The names are the
# trainer's public + jitted-impl surface; a repo-specific harness can
# mark extra ones hot with a matching name.
HOT_FUNCTIONS = frozenset(
    {
        "fit",
        "evaluate",
        "train_step",
        "eval_step",
        "train_step_placed",
        "_train_step_impl",
        "_eval_step_impl",
        # The seam's per-step and per-boundary events (obs/fit_observers.py):
        # what listens to fit's loop is held to the loop's discipline.
        "before_step",
        "after_step",
        "log",
        "logged",
    }
)

# jax.random derivation fns — NOT consumers; everything else under
# jax.random that takes a key as its first argument consumes it.
_KEY_DERIVERS = frozenset(
    {"split", "fold_in", "PRNGKey", "key", "key_data", "wrap_key_data", "clone"}
)

_TIME_CALLS = frozenset(
    {
        "time.time",
        "time.time_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.monotonic",
        "time.process_time",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
    }
)

# Paths whose code runs under bf16 compute by default (the model zoo and
# the device-side ops): an f32-defaulting constructor here silently
# promotes every downstream op (docs/static_analysis.md, SAV108).
BF16_PATHS = ("sav_tpu/models/", "sav_tpu/ops/")


def _finding(rule, node, message, hint="", code=""):
    return Finding(
        rule=rule.id,
        severity=rule.severity,
        path="",
        line=getattr(node, "lineno", 1),
        col=getattr(node, "col_offset", 0),
        message=message,
        hint=hint or rule.hint,
        code=code,
        end_line=getattr(node, "end_lineno", 0) or getattr(node, "lineno", 1),
    )


def _walk_excluding_nested(fn) -> Iterator[ast.AST]:
    """Nodes of ``fn``'s body, not descending into nested function/lambda.

    For thread- and hot-loop-scoped rules: a closure handed to a feeder
    runs on another thread (or inside a trace) and must be judged in its
    own scope, not its parent's.
    """
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class Rule:
    id: str = ""
    name: str = ""
    severity: str = "error"
    hint: str = ""

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------- SAV101


class HostSyncInHotLoop(Rule):
    """Host synchronization reachable from the training hot path.

    ``jax.device_get`` / ``block_until_ready`` / ``.item()`` /
    ``np.asarray`` inside ``fit()``, ``evaluate()``, or a jitted step
    implementation forces the dispatch pipeline to drain: the host
    blocks until the device catches up, the device then idles until the
    host dispatches again — the serialization PR 2's feeder exists to
    remove. ``float(x[...])``/``int(x.attr)`` are the same sync in
    disguise (implicit ``__float__`` on a device scalar). Legitimate
    sites exist — the per-log-window metrics sync, eval's single
    end-of-pass ``device_get``, the run-ahead cap — and each must be
    allowlisted with a pragma stating why, so the next reader knows the
    sync is priced in rather than accidental.
    """

    id = "SAV101"
    name = "host-sync-in-hot-loop"
    severity = "error"
    hint = (
        "keep values on device (stack/sum device-side, one device_get at a "
        "boundary); if this sync is intentional, pragma it with a "
        "justification"
    )

    SYNC_CALLS = {
        "jax.device_get": "jax.device_get blocks on the device",
        "jax.block_until_ready": "jax.block_until_ready drains the pipeline",
        "numpy.asarray": "np.asarray on a device array is a blocking D2H copy",
        "numpy.array": "np.array on a device array is a blocking D2H copy",
    }
    SYNC_METHODS = {
        "item": ".item() pulls a device scalar to host",
        "block_until_ready": ".block_until_ready() drains the pipeline",
    }

    def check(self, module):
        seen: set[int] = set()
        for fn in module.functions:
            if fn.name not in HOT_FUNCTIONS:
                continue
            for node in ast.walk(fn):
                if id(node) in seen or not isinstance(node, ast.Call):
                    continue
                seen.add(id(node))
                resolved = module.resolve_call(node)
                where = f"in hot function {fn.name}()"
                if resolved in self.SYNC_CALLS:
                    yield _finding(
                        self, node, f"{self.SYNC_CALLS[resolved]} {where}"
                    )
                    continue
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.SYNC_METHODS
                    and not node.args
                    and not node.keywords
                ):
                    yield _finding(
                        self,
                        node,
                        f"{self.SYNC_METHODS[node.func.attr]} {where}",
                    )
                    continue
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int")
                    and len(node.args) == 1
                    and isinstance(node.args[0], (ast.Subscript, ast.Attribute))
                ):
                    yield _finding(
                        self,
                        node,
                        f"{node.func.id}() on a subscript/attribute {where} "
                        "implicitly syncs a device scalar to host",
                    )


# ---------------------------------------------------------------- SAV102


class JitWithoutDonation(Rule):
    """State-carrying step function jitted without buffer donation.

    A train step that takes the parameter/optimizer state and returns
    the next state must donate it (``donate_argnums``): without donation
    XLA keeps both generations of every buffer live across the update —
    on a memory-bound model that is the difference between fitting and
    OOM, and it costs an extra copy either way. Functions with ``eval``
    or ``init`` in their name are exempt: eval reuses the state across
    batches (donating it would be a use-after-donate crash) and init has
    nothing to donate.
    """

    id = "SAV102"
    name = "jit-without-donation"
    severity = "warning"
    hint = (
        "jax.jit(step, donate_argnums=(0,)) so the old state's buffers are "
        "reused in place"
    )

    STATE_PARAMS = frozenset({"state", "train_state", "opt_state"})

    def _first_param(self, fn):
        args = list(fn.args.posonlyargs) + list(fn.args.args)
        names = [a.arg for a in args]
        if names and names[0] == "self":
            names = names[1:]
        return names[0] if names else None

    def _exempt(self, name: str) -> bool:
        return "eval" in name or "init" in name

    def check(self, module):
        by_name = {}
        for fn in module.functions:
            by_name.setdefault(fn.name, fn)
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            if module.resolve_call(node) != "jax.jit" or not node.args:
                continue
            kwargs = {k.arg for k in node.keywords}
            if kwargs & {"donate_argnums", "donate_argnames"}:
                continue
            target = _bare_name(node.args[0])
            fn = by_name.get(target) if target else None
            if fn is None or self._exempt(fn.name):
                continue
            if self._first_param(fn) in self.STATE_PARAMS:
                yield _finding(
                    self,
                    node,
                    f"jax.jit({target}) carries state (first parameter "
                    f"{self._first_param(fn)!r}) but donates nothing — both "
                    "state generations stay live across every step",
                )
        # Decorator forms: bare @jax.jit cannot pass donate_argnums at
        # all; @partial(jax.jit, ...) can but may have forgotten to.
        for fn in module.jitted_defs:
            if self._exempt(fn.name) or (
                self._first_param(fn) not in self.STATE_PARAMS
            ):
                continue
            for dec in fn.decorator_list:
                if module.resolve(dec) == "jax.jit":
                    yield _finding(
                        self,
                        dec,
                        f"@jax.jit on {fn.name}() carries state but a bare "
                        "decorator cannot donate",
                        hint="use @partial(jax.jit, donate_argnums=(0,))",
                    )
                elif (
                    isinstance(dec, ast.Call)
                    and module.resolve_call(dec)
                    in ("functools.partial", "partial")
                    and dec.args
                    and module.resolve(dec.args[0]) == "jax.jit"
                    and not (
                        {k.arg for k in dec.keywords}
                        & {"donate_argnums", "donate_argnames"}
                    )
                ):
                    yield _finding(
                        self,
                        dec,
                        f"@partial(jax.jit) on {fn.name}() carries state "
                        "but donates nothing — both state generations stay "
                        "live across every step",
                    )


# ---------------------------------------------------------------- SAV103


class PrngKeyReuse(Rule):
    """The same PRNG key consumed by more than one random op.

    Two samplers fed the same key draw *correlated* values — dropout
    masks equal to stochastic-depth draws, augmentation mixes mirroring
    initialization noise. The failure is silent: shapes check out,
    training even converges, just worse. Keys must be split
    (``jax.random.split``) or derived (``fold_in``) per consumer;
    deriving does not count as consumption. The check is per-scope and
    flow-insensitive (an if/else consuming the same key once per branch
    is a false positive worth a pragma).
    """

    id = "SAV103"
    name = "prng-key-reuse"
    severity = "error"
    hint = (
        "split the key per consumer (k1, k2 = jax.random.split(key)) or "
        "derive with jax.random.fold_in(key, tag)"
    )

    def check(self, module):
        for fn in module.functions:
            events = []  # (line, col, kind, name, node)
            for node in _walk_excluding_nested(fn):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name):
                                events.append(
                                    (leaf.lineno, leaf.col_offset, "assign",
                                     leaf.id, None)
                                )
                elif isinstance(node, ast.Call):
                    resolved = module.resolve_call(node)
                    if not resolved or not resolved.startswith("jax.random."):
                        continue
                    leaf_fn = resolved.rsplit(".", 1)[1]
                    if leaf_fn in _KEY_DERIVERS:
                        continue
                    if node.args and isinstance(node.args[0], ast.Name):
                        events.append(
                            (node.lineno, node.col_offset, "consume",
                             node.args[0].id, node)
                        )
            events.sort(key=lambda e: (e[0], e[1]))
            consumed: dict[str, int] = {}
            for line, _col, kind, name, node in events:
                if kind == "assign":
                    consumed.pop(name, None)
                else:
                    first = consumed.get(name)
                    if first is None:
                        consumed[name] = line
                    else:
                        yield _finding(
                            self,
                            node,
                            f"key {name!r} already consumed at line {first} "
                            f"in {fn.name}() and is consumed again here — "
                            "the two draws are correlated",
                        )


# ---------------------------------------------------------------- SAV104


class PythonScalarArgRetrace(Rule):
    """A loop-varying Python scalar passed straight into a jitted call.

    ``step(state, i)`` inside ``for i in range(n)`` hurts either way the
    scalar is treated: marked static, jit compiles one program per
    distinct value — ``n`` retraces, each seconds to minutes; left
    dynamic, the scalar is implicitly uploaded host→device on every
    single call (the transfer sanitizer flags exactly this at runtime).
    Loop counters belong on device (fold them into the carried state,
    like ``state.step``) or in the data, never in the jitted call's
    Python arguments.
    """

    id = "SAV104"
    name = "python-scalar-arg-retrace"
    severity = "error"
    hint = (
        "carry the counter in device state (state.step), pass it as a "
        "jnp array, or mark the parameter static on purpose"
    )

    def _int_loop_vars(self, loop: ast.For):
        """Loop targets that are Python ints: range() binds every target,
        enumerate() binds the first element of a tuple target."""
        if not isinstance(loop.iter, ast.Call):
            return set()
        if not isinstance(loop.iter.func, ast.Name):
            return set()
        fn = loop.iter.func.id
        if fn == "range":
            return {
                n.id for n in ast.walk(loop.target) if isinstance(n, ast.Name)
            }
        if fn == "enumerate" and isinstance(loop.target, ast.Tuple):
            first = loop.target.elts[0]
            if isinstance(first, ast.Name):
                return {first.id}
        return set()

    def check(self, module):
        if not module.jitted_names:
            return
        for loop in module.nodes:
            if not isinstance(loop, ast.For):
                continue
            loop_vars = self._int_loop_vars(loop)
            if not loop_vars:
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                callee = _bare_name(node.func)
                if callee not in module.jitted_names:
                    continue
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    bad = (
                        isinstance(arg, ast.Name) and arg.id in loop_vars
                    ) or (
                        isinstance(arg, ast.BinOp)
                        and any(
                            isinstance(n, ast.Name) and n.id in loop_vars
                            for n in ast.walk(arg)
                        )
                    )
                    if bad:
                        yield _finding(
                            self,
                            node,
                            f"jitted {callee}() receives the Python loop "
                            "counter as an argument — a retrace per value "
                            "if static, an implicit host→device upload "
                            "every call if not",
                        )
                        break


# ---------------------------------------------------------------- SAV105


class TimeInJit(Rule):
    """Wall-clock calls inside jit-traced code.

    ``time.time()`` in a jitted function runs **once, at trace time**:
    the value is baked into the compiled program as a constant, so the
    "timestamp" never advances and any timing math built on it is
    silently wrong (and differs between a cached and a fresh compile).
    Timing belongs on the host, around the dispatch — the span tracer
    and goodput ledger (PR 1) exist for exactly this.
    """

    id = "SAV105"
    name = "time-in-jit"
    severity = "error"
    hint = (
        "time on the host around the jitted call (obs.spans / "
        "obs.goodput), never inside the trace"
    )

    def check(self, module):
        seen: set[int] = set()
        for fn in module.jitted_defs:
            for node in ast.walk(fn):
                if id(node) in seen or not isinstance(node, ast.Call):
                    continue
                seen.add(id(node))
                resolved = module.resolve_call(node)
                if resolved in _TIME_CALLS:
                    yield _finding(
                        self,
                        node,
                        f"{resolved}() inside jitted {fn.name}() is evaluated "
                        "once at trace time and frozen into the program",
                    )


# ---------------------------------------------------------------- SAV106


class InlineDevicePutInFit(Rule):
    """Blocking device placement on the training thread's hot loop.

    With the async feeder on (the default since PR 2), every sharded
    ``device_put`` belongs to the feeder's background thread; a
    ``device_put``/``shard_batch`` call in ``fit()`` or ``evaluate()``
    re-serializes host→device transfer into the critical path and
    quietly undoes the overlap the feeder bought. This rule is the
    static home of the invariant tests/test_feeder.py used to assert by
    instrumenting threads; the serial fallback path
    (``async_feed=False``) is the one sanctioned exception and carries
    the pragma. Closures are exempt — a ``place`` closure handed to the
    feeder *runs on the feeder thread*.
    """

    id = "SAV106"
    name = "inline-device-put-in-fit"
    severity = "error"
    hint = (
        "route placement through the DeviceFeeder (async_feed) so the "
        "transfer overlaps device compute; see docs/input_pipeline.md"
    )

    PLACE_CALLS = {"jax.device_put", "jax.make_array_from_process_local_data"}
    PLACE_METHODS = {"shard_batch"}

    def check(self, module):
        for fn in module.functions:
            if fn.name not in ("fit", "evaluate"):
                continue
            for node in _walk_excluding_nested(fn):
                if not isinstance(node, ast.Call):
                    continue
                resolved = module.resolve_call(node)
                callee = _bare_name(node.func)
                if resolved in self.PLACE_CALLS or callee in self.PLACE_METHODS:
                    yield _finding(
                        self,
                        node,
                        f"inline device placement ({callee}) on the training "
                        f"thread in {fn.name}() — transfer serializes into "
                        "the hot loop instead of overlapping via the feeder",
                    )


# ---------------------------------------------------------------- SAV107


class UnlockedThreadSharedState(Rule):
    """Cross-thread attribute writes without a lock.

    A class that starts a ``threading.Thread`` on one of its own methods
    (the feeder/watchdog pattern) shares ``self`` between threads; an
    attribute the worker method writes *and* another method also writes
    is a data race unless every write holds a lock. Single-writer
    telemetry counters (worker writes, others only read) are fine and
    not flagged; ``__init__`` writes happen before the thread starts and
    are likewise exempt.
    """

    id = "SAV107"
    name = "unlocked-thread-shared-state"
    severity = "warning"
    hint = (
        "guard multi-writer attributes with one threading.Lock (with "
        "self._lock: ...), or restructure so only one thread writes"
    )

    def _lockish(self, node, lock_attrs) -> bool:
        if isinstance(node, ast.Attribute):
            return node.attr in lock_attrs or "lock" in node.attr.lower()
        if isinstance(node, ast.Name):
            return "lock" in node.id.lower()
        return False

    def _method_writes(self, method, lock_attrs):
        """(attr, node, protected) for every self.attr assignment."""
        out = []

        def visit(node, protected):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return
            if isinstance(node, ast.With):
                held = protected or any(
                    self._lockish(item.context_expr, lock_attrs)
                    for item in node.items
                )
                for child in ast.iter_child_nodes(node):
                    visit(child, held)
                return
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for t in targets:
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    out.append((t.attr, node, protected))
            for child in ast.iter_child_nodes(node):
                visit(child, protected)

        for stmt in method.body:
            visit(stmt, False)
        return out

    def check(self, module):
        for cls in module.nodes:
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = [
                n
                for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            ]
            workers = set()
            lock_attrs = set()
            for node in ast.walk(cls):
                if isinstance(node, ast.Call):
                    if module.resolve_call(node) == "threading.Thread":
                        for k in node.keywords:
                            if (
                                k.arg == "target"
                                and isinstance(k.value, ast.Attribute)
                                and isinstance(k.value.value, ast.Name)
                                and k.value.value.id == "self"
                            ):
                                workers.add(k.value.attr)
                if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call
                ):
                    resolved = module.resolve_call(node.value)
                    if resolved in (
                        "threading.Lock",
                        "threading.RLock",
                        "threading.Condition",
                        "threading.Semaphore",
                    ):
                        for t in node.targets:
                            if (
                                isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"
                            ):
                                lock_attrs.add(t.attr)
            if not workers:
                continue
            writes = {
                m.name: self._method_writes(m, lock_attrs) for m in methods
            }
            writers_of: dict[str, set] = {}
            for name, ws in writes.items():
                if name == "__init__":
                    continue
                for attr, _node, _prot in ws:
                    writers_of.setdefault(attr, set()).add(name)
            for attr, method_names in writers_of.items():
                if len(method_names) < 2 or not (method_names & workers):
                    continue
                for name in method_names:
                    for wattr, node, protected in writes[name]:
                        if wattr != attr or protected:
                            continue
                        yield _finding(
                            self,
                            node,
                            f"self.{attr} is written by "
                            f"{sorted(method_names)} while "
                            f"{sorted(method_names & workers)} runs on its "
                            "own thread — unlocked multi-writer state",
                        )


# ---------------------------------------------------------------- SAV108


class F32LiteralPromotion(Rule):
    """dtype-less float array constructor in a bf16 compute path.

    ``jnp.zeros(shape)`` defaults to float32; under bf16 compute that
    constant promotes every op it touches back to f32 — doubling the HBM
    traffic the bf16 path existed to halve, invisibly (results stay
    correct, the step just gets slower; PERF.md §6 measured the
    [B,H,L,L] case at −15% step time). Scoped to the model/ops trees
    where compute dtype is a parameter; int-valued ``arange`` is exempt.
    """

    id = "SAV108"
    name = "f32-literal-promotion"
    severity = "warning"
    hint = (
        "pass the computation's dtype explicitly "
        "(jnp.zeros(shape, dtype=x.dtype) or the module's self.dtype)"
    )

    # constructor → index of the positional dtype parameter
    CTORS = {
        "jax.numpy.zeros": 1,
        "jax.numpy.ones": 1,
        "jax.numpy.empty": 1,
        "jax.numpy.full": 2,
    }

    def check(self, module):
        if not module.relpath.startswith(BF16_PATHS):
            return
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            resolved = module.resolve_call(node)
            if resolved in self.CTORS:
                if any(k.arg == "dtype" for k in node.keywords):
                    continue
                if len(node.args) > self.CTORS[resolved]:
                    continue  # positional dtype
                yield _finding(
                    self,
                    node,
                    f"{resolved.rsplit('.', 1)[1]}() without dtype defaults "
                    "to float32 and promotes the surrounding bf16 compute",
                )
            elif resolved == "jax.numpy.linspace":
                if not any(k.arg == "dtype" for k in node.keywords):
                    yield _finding(
                        self,
                        node,
                        "linspace() without dtype defaults to float32 and "
                        "promotes the surrounding bf16 compute",
                    )
            elif resolved == "jax.numpy.arange":
                has_float = any(
                    isinstance(a, ast.Constant) and isinstance(a.value, float)
                    for a in node.args
                )
                if has_float and not any(
                    k.arg == "dtype" for k in node.keywords
                ) and len(node.args) < 4:
                    yield _finding(
                        self,
                        node,
                        "arange() over floats without dtype defaults to "
                        "float32 and promotes the surrounding bf16 compute",
                    )


# ---------------------------------------------------------------- SAV109


class JitInLoop(Rule):
    """``jax.jit`` called inside a loop body.

    ``jax.jit`` keys its compile cache on the *function object*: wrapping
    a fresh lambda/closure each iteration means a cache miss — trace and
    compile — every time around the loop. Hoist the jit outside the loop
    (or module scope) and call the one wrapped function repeatedly.
    """

    id = "SAV109"
    name = "jit-in-loop"
    severity = "warning"
    hint = "hoist the jax.jit(...) wrapping out of the loop; jit once, call many"

    def check(self, module):
        def visit(node, in_loop):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                in_loop = False
            elif isinstance(node, (ast.For, ast.While)):
                in_loop = True
            elif (
                in_loop
                and isinstance(node, ast.Call)
                and module.resolve_call(node) == "jax.jit"
            ):
                yield _finding(
                    self,
                    node,
                    "jax.jit inside a loop wraps a fresh function object "
                    "per iteration — a compile-cache miss every time",
                )
            for child in ast.iter_child_nodes(node):
                yield from visit(child, in_loop)

        yield from visit(module.tree, False)


# ---------------------------------------------------------------- SAV110


class AdhocSeedDerivation(Rule):
    """Arithmetic on seeds instead of ``fold_in`` on a key.

    ``PRNGKey(seed + 1)`` manufactures a sibling stream by poking the
    seed — nothing stops ``seed + 1`` from colliding with another run's
    ``seed``, and the derivation is invisible to anyone auditing key
    lineage. ``jax.random.fold_in(run_key, tag)`` derives a
    statistically independent stream from the run key with an explicit,
    greppable tag (trainer.py's fit() key is the in-repo example).
    """

    id = "SAV110"
    name = "adhoc-seed-derivation"
    severity = "warning"
    hint = (
        "derive from the run key: jax.random.fold_in("
        "jax.random.PRNGKey(seed), tag)"
    )

    def check(self, module):
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            if module.resolve_call(node) != "jax.random.PRNGKey":
                continue
            if node.args and isinstance(node.args[0], ast.BinOp):
                yield _finding(
                    self,
                    node,
                    "PRNGKey over seed arithmetic — derive sibling streams "
                    "with fold_in on the run key, not by perturbing the seed",
                )


# ---------------------------------------------------------------- SAV111


def _metric_rooted(node) -> bool:
    """True when the expression is rooted at a metrics-named value."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return isinstance(node, ast.Name) and "metric" in node.id.lower()


def _metrics_sync_findings(rule, module, fn, *, where: str, coda: str):
    """Sync detection shared by the recorder (SAV111) and fleet (SAV112)
    hot-path rules: explicit sync calls/methods, and ``float()``/
    ``int()`` pulling a metrics-named value (bare or rooted) to host
    through ``__float__``. One definition so a new sync API or a
    heuristic fix lands in both rules at once."""
    for node in _walk_excluding_nested(fn):
        if not isinstance(node, ast.Call):
            continue
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int")
            and len(node.args) == 1
        ):
            arg = node.args[0]
            if isinstance(arg, ast.Name) and "metric" in arg.id.lower():
                yield _finding(
                    rule,
                    node,
                    f"{node.func.id}() on step metrics in {where} "
                    f"{fn.name}() implicitly syncs a device scalar to "
                    "host",
                )
                continue
            if (
                isinstance(arg, (ast.Subscript, ast.Attribute))
                and _metric_rooted(arg)
            ):
                yield _finding(
                    rule,
                    node,
                    f"{node.func.id}() on a metrics subscript/attribute "
                    f"in {where} {fn.name}() implicitly syncs a device "
                    "scalar to host",
                )
                continue
        resolved = module.resolve_call(node)
        if resolved in HostSyncInHotLoop.SYNC_CALLS:
            yield _finding(
                rule,
                node,
                f"{HostSyncInHotLoop.SYNC_CALLS[resolved]} in {where} "
                f"{fn.name}() — {coda}",
            )
            continue
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in HostSyncInHotLoop.SYNC_METHODS
            and not node.args
            and not node.keywords
        ):
            yield _finding(
                rule,
                node,
                f"{HostSyncInHotLoop.SYNC_METHODS[node.func.attr]} in "
                f"{where} {fn.name}() — {coda}",
            )


class RecorderHotLoopSync(Rule):
    """Host sync on step metrics inside the recorded hot loop.

    The flight recorder's steady-state contract (sav_tpu/obs/recorder.py,
    docs/incident_replay.md) is that recording adds **no per-step device
    syncs**: the per-step path (``observe_batch``/``on_step``) is host
    bookkeeping only, and detection (``note_metrics``) runs on metrics
    the trainer *already* ``device_get``'d at its log boundary. Two ways
    an edit silently breaks that: a sync call slipped into one of the
    recorder's per-step functions (they are outside SAV101's
    fit/evaluate scope, so SAV111 owns them), or a ``float(metrics)`` /
    ``int(metric_dict)`` on a bare metrics-named value in the hot loop —
    a device scalar pulled to host through ``__float__``, invisible to
    SAV101's subscript/attribute heuristic. Sanctioned sync points carry
    the usual justification pragma.
    """

    id = "SAV111"
    name = "recorder-hot-loop-sync"
    severity = "error"
    hint = (
        "keep the recorder's per-step path host-only (detection rides the "
        "trainer's existing log-boundary device_get); if this sync is the "
        "sanctioned periodic snapshot, pragma it with a justification"
    )

    # The recorder's per-step surface: judged like the trainer's hot loop,
    # but by this rule (SAV101's HOT_FUNCTIONS stays fit/evaluate/steps).
    RECORDER_FUNCTIONS = frozenset(
        {"observe_batch", "on_step", "note_metrics", "wrap_place"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.RECORDER_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="recorder hot path",
                    coda="recording must not add per-step syncs",
                )
            elif fn.name in HOT_FUNCTIONS:
                # In fit/evaluate only the implicit-__float__ sync on a
                # BARE metrics name is this rule's beat (SAV101's
                # subscript/attribute heuristic cannot see it); the
                # rest of the hot-loop sync catalogue is SAV101's.
                for node in _walk_excluding_nested(fn):
                    if (
                        isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int")
                        and len(node.args) == 1
                        and isinstance(node.args[0], ast.Name)
                        and "metric" in node.args[0].id.lower()
                    ):
                        yield _finding(
                            self,
                            node,
                            f"{node.func.id}() on step metrics in "
                            f"{fn.name}() implicitly syncs a device "
                            "scalar to host",
                        )


# ---------------------------------------------------------------- SAV112


class FleetHotPathSync(Rule):
    """Host sync in the fleet-telemetry / anomaly-profiler hot path.

    The fleet layer's steady-state contract (sav_tpu/obs/fleet.py,
    sav_tpu/obs/autoprof.py, docs/fleet.md) mirrors the flight
    recorder's (SAV111): a heartbeat is one appended JSON line built
    from values that are *already* host-side at the trainer's log
    boundary — the goodput ledger's wall-clock aggregates and the
    metrics dict fit() synced anyway — and the profiler's arm/disarm
    path is pure host bookkeeping. A ``device_get`` /
    ``block_until_ready`` / ``.item()`` slipped into ``beat()`` /
    ``fleet_event()`` / ``note_window()`` / ``request()``, or a
    ``float(metrics...)`` pulling a device scalar through
    ``__float__``, would turn every logging window into a pipeline
    drain across the whole fleet. These functions sit outside SAV101's
    fit/evaluate scope (and outside SAV111's recorder set), so SAV112
    owns them.
    """

    id = "SAV112"
    name = "fleet-hot-path-sync"
    severity = "error"
    hint = (
        "keep the fleet heartbeat/autoprof path host-only (heartbeats "
        "carry values the trainer already synced at its log boundary); "
        "if a sync here is truly intentional, pragma it with a "
        "justification"
    )

    # The fleet layer's per-beat surface. Deliberately DISJOINT from
    # SAV111's RECORDER_FUNCTIONS — overlapping scopes would double-
    # report the same call. GoodputLedger.note_window shares a name and
    # the same obligation (host math only), so the rule covers it too.
    FLEET_FUNCTIONS = frozenset(
        {"beat", "fleet_event", "note_window", "request"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.FLEET_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="fleet hot path",
                    coda="heartbeating must not add device syncs",
                )


# ---------------------------------------------------------------- SAV113


class ProfilerInHotPath(Rule):
    """``jax.profiler`` / memory-forensics calls in the training hot path.

    The profiling contract (docs/profiling.md) is that capture happens
    through the *armed windows* — the edge-synced static window
    (``TrainConfig.profile_dir``), autoprof's bounded anomaly captures,
    the OOM incident path — never ad hoc inside the hot loop. A stray
    ``start_trace``/``stop_trace`` serializes dispatch and bloats the
    trace ring on every step; ``save_device_memory_profile`` /
    ``live_arrays`` walk every live buffer; ``dump_memory_incident``
    writes a forensics bundle. All are incident/window machinery, and in
    ``fit()``/``evaluate()``/the step impls they are a steady-state tax
    that the telemetry guards (<1-2% overhead contracts) cannot see
    statically. The sanctioned sites — the static window's edges, the
    OOM dump in fit's finally — carry justification pragmas.
    """

    id = "SAV113"
    name = "profiler-in-hot-path"
    severity = "error"
    hint = (
        "capture through the armed windows (TrainConfig.profile_dir, "
        "autoprof's anomaly captures) or the incident path; a sanctioned "
        "window-edge/incident call carries a justification pragma"
    )

    PROFILER_CALLS = {
        "jax.profiler.start_trace": "jax.profiler.start_trace",
        "jax.profiler.stop_trace": "jax.profiler.stop_trace",
        "jax.profiler.trace": "jax.profiler.trace window",
        "jax.profiler.save_device_memory_profile":
            "device-memory pprof dump",
        "jax.profiler.device_memory_profile": "device-memory profile",
        "jax.live_arrays": "live-buffer walk",
        "sav_tpu.utils.profiler.start_trace": "profiler.start_trace",
        "sav_tpu.utils.profiler.stop_trace": "profiler.stop_trace",
        "sav_tpu.utils.profiler.trace": "profiler trace window",
        "sav_tpu.obs.memdump.dump_memory_incident":
            "memory-forensics dump",
        "sav_tpu.obs.memdump.live_buffer_ranking": "live-buffer ranking",
        "sav_tpu.obs.memdump.live_bytes_total": "live-buffer walk",
        "sav_tpu.obs.memdump.save_device_memory_profile":
            "device-memory pprof dump",
    }

    def check(self, module):
        for fn in module.functions:
            if fn.name not in HOT_FUNCTIONS:
                continue
            for node in _walk_excluding_nested(fn):
                if not isinstance(node, ast.Call):
                    continue
                resolved = module.resolve_call(node)
                if resolved in self.PROFILER_CALLS:
                    yield _finding(
                        self,
                        node,
                        f"{self.PROFILER_CALLS[resolved]} in {fn.name}() "
                        "— profiling/forensics belong to the armed "
                        "windows or the incident path, not the hot loop",
                    )


# ---------------------------------------------------------------- SAV114


class BareExitInLibrary(Rule):
    """``sys.exit`` / ``os._exit`` / ``raise SystemExit`` in library code.

    The elasticity layer (docs/elasticity.md) depends on a strict
    exit-code contract: 0 ok, 2 usage, 3 backend-unreachable, 4 hang —
    and on every abnormal exit flowing through the paths that finalize
    the run manifest, drain in-flight async checkpoint saves, and dump
    incident bundles. A bare exit buried in ``sav_tpu/`` breaks both at
    once: ``sys.exit`` raises ``SystemExit`` from an arbitrary depth
    (callers' except-Exception blocks don't see it; an unexpected code
    confuses supervisors into misclassifying the restart reason), and
    ``os._exit`` skips every finally/atexit — the crash telemetry the
    whole obs stack exists to write. Library code raises exceptions;
    only the CLIs (train.py, bench.py, tools/) own process exit. The two
    sanctioned library sites — the hang watchdog's ``os._exit`` (a
    wedged main thread cannot be unwound) and the device check's
    ``SystemExit(3)`` (the documented abort contract) — carry
    justification pragmas, and ``os._exit`` *references* are findings
    too (handing the capability around is how it escapes audit).
    """

    id = "SAV114"
    name = "bare-exit-in-library"
    severity = "error"
    hint = (
        "raise a typed exception and let the CLI own process exit; the "
        "watchdog/probe contracts are the only sanctioned library exits "
        "and carry justification pragmas"
    )

    EXIT_CALLS = {
        "sys.exit": "sys.exit() raises SystemExit from library depth",
        "os._exit": "os._exit() skips every finally/atexit "
                    "(manifest finalize, checkpoint drain, incident dumps)",
    }
    LIBRARY_PREFIX = "sav_tpu/"

    def check(self, module):
        if not module.relpath.startswith(self.LIBRARY_PREFIX):
            return  # CLIs and tools legitimately own process exit
        consumed_funcs = set()
        for node in module.nodes:
            if isinstance(node, ast.Call):
                resolved = module.resolve_call(node)
                if resolved in self.EXIT_CALLS:
                    consumed_funcs.add(id(node.func))
                    yield _finding(
                        self, node,
                        f"{self.EXIT_CALLS[resolved]} — library code must "
                        "raise, not exit",
                    )
            elif isinstance(node, ast.Raise):
                exc = node.exc
                name = None
                if isinstance(exc, ast.Call) and isinstance(
                    exc.func, ast.Name
                ):
                    name = exc.func.id
                elif isinstance(exc, ast.Name):
                    name = exc.id
                if name == "SystemExit":
                    yield _finding(
                        self, node,
                        "raise SystemExit in library code — callers' "
                        "except-Exception blocks never see it; raise a "
                        "typed error and let the CLI exit",
                    )
        for node in module.nodes:
            # Bare references (default args, callbacks): handing the
            # hard-exit capability around is how it escapes audit.
            if (
                isinstance(node, (ast.Attribute, ast.Name))
                and id(node) not in consumed_funcs
                and module.resolve(node) in self.EXIT_CALLS
            ):
                yield _finding(
                    self, node,
                    f"reference to {module.resolve(node)} in library code "
                    "— the exit capability itself needs a pragma'd "
                    "contract, not a pass-around",
                )


# ---------------------------------------------------------------- SAV115


class ServeHotLoopSync(Rule):
    """Host sync in the serving batcher's admission/drain path.

    The serving engine's steady-state contract (sav_tpu/serve/,
    docs/serving.md) mirrors the training hot loop's: request admission
    (``submit``/``submit_raw``), batch forming (``next_batch`` and the
    engine's ``_formed_batches`` drain iterator) and placement
    (``_place_formed``, which runs on the feeder thread so the
    device_put of batch N+1 overlaps batch N's execution) are host-only
    bookkeeping. The ONE device sync per shipped batch is the device
    loop's post-execution result fetch. A ``device_get`` /
    ``block_until_ready`` / ``.item()`` slipped into the drain — e.g. a
    per-request result read inside ``next_batch`` — would serialize
    every request behind a pipeline drain and void both the overlap and
    the p99 budget. These functions sit outside SAV101's fit/evaluate
    scope (and outside SAV111/SAV112's sets), so SAV115 owns them.
    """

    id = "SAV115"
    name = "serve-hot-loop-sync"
    severity = "error"
    hint = (
        "keep admission/drain/placement host-only; results sync ONCE per "
        "shipped batch in the device loop — if a sync here is truly "
        "intentional, pragma it with a justification"
    )

    # The serving hot path's surface. Disjoint from SAV101's
    # HOT_FUNCTIONS and SAV111/SAV112's sets (overlap would double-report).
    SERVE_FUNCTIONS = frozenset(
        {"submit", "submit_raw", "next_batch", "_formed_batches",
         "_place_formed"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.SERVE_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="serve hot path",
                    coda="the batcher drain must not sync",
                )


# ---------------------------------------------------------------- SAV116


class ServeTelemetryHotPathSync(Rule):
    """Host sync in the serve-telemetry span/window/heartbeat path.

    The serve telemetry layer (sav_tpu/serve/telemetry.py,
    docs/serving.md) rides INSIDE the paths SAV115 keeps sync-free: span
    stamps fire in the batcher's admission/drain and the engine's device
    loop, window observation runs on every completed batch, and the
    heartbeat thread snapshots windows that those paths feed. The
    contract mirrors the recorder's (SAV111) and fleet's (SAV112):
    every value a stamp/window/heartbeat touches is already host-side —
    monotonic clock reads, the latency floats the device loop computed
    after its one sanctioned sync. A ``device_get`` /
    ``block_until_ready`` / ``.item()`` slipped into ``stamp()`` /
    ``begin_trace()`` / ``observe_window()`` / ``observe_completed()``
    / ``observe_shed()`` / ``serve_beat()``, or a ``float(metrics...)``
    pulling a device scalar through ``__float__``, would serialize the
    batcher drain or the device loop behind a pipeline drain and void
    the p99 the telemetry exists to report. These functions sit outside
    SAV101's fit/evaluate scope and outside SAV111/SAV112/SAV115's
    sets, so SAV116 owns them.
    """

    id = "SAV116"
    name = "serve-telemetry-hot-path-sync"
    severity = "error"
    hint = (
        "keep span stamps / window observation / heartbeats host-only "
        "(the device loop's ONE post-execution fetch already synced "
        "every value telemetry needs); if a sync here is truly "
        "intentional, pragma it with a justification"
    )

    # The serve-telemetry hot surface. Deliberately DISJOINT from
    # SAV101's HOT_FUNCTIONS, SAV111's recorder set ("observe_batch" —
    # which also covers LatencyLedger.observe_batch), SAV112's fleet set
    # ("beat"/"note_window"/"request") and SAV115's serve set (overlap
    # would double-report the same call).
    TELEMETRY_FUNCTIONS = frozenset(
        {"stamp", "begin_trace", "observe_window", "observe_completed",
         "observe_shed", "serve_beat"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.TELEMETRY_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="serve telemetry hot path",
                    coda="span/window/heartbeat telemetry must not sync",
                )


# ---------------------------------------------------------------- SAV118


class RouterHotPathSync(Rule):
    """Host sync in the fleet router's admit/route/drain path.

    The fleet router (sav_tpu/serve/router.py, docs/serving.md "Fleet")
    is the one component EVERY request in the fleet passes through: its
    admission projection, replica choice, completion bookkeeping, and
    view refresh run on the submit path or the dispatch workers, and
    every value they touch is host-side by construction — parsed
    heartbeat JSON, wall clocks, the router's own counters (the module
    is stdlib-only; jax is structurally unimportable from it). A
    ``device_get`` / ``block_until_ready`` / ``.item()`` slipped into
    ``admit()`` / ``route()`` / ``note_result()`` / ``_refresh_views()``
    / ``drain()`` / ``resume()``, or a ``float(metrics...)`` pulling a
    device scalar through ``__float__``, would serialize every request
    in the FLEET behind one pipeline drain — the whole-fleet version of
    the failure SAV115 guards one replica against. These functions sit
    outside SAV101's fit/evaluate scope and outside
    SAV111/SAV112/SAV115/SAV116's sets, so SAV118 owns them.
    """

    id = "SAV118"
    name = "router-hot-path-sync"
    severity = "error"
    hint = (
        "keep the router's admission/routing/drain path host-only (it "
        "routes on parsed heartbeat lines and its own counters — no "
        "device value belongs in reach); if a sync here is truly "
        "intentional, pragma it with a justification"
    )

    # The router's hot surface. Deliberately DISJOINT from SAV101's
    # HOT_FUNCTIONS and the SAV111/SAV112/SAV115/SAV116 sets (overlap
    # would double-report the same call).
    ROUTER_FUNCTIONS = frozenset(
        {"admit", "route", "note_result", "_refresh_views", "drain",
         "resume"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.ROUTER_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="router hot path",
                    coda="routing must not sync the whole fleet",
                )


class RouterTraceHotPathSync(Rule):
    """Host sync in the fleet router's TRACING surface (ISSUE 16).

    The distributed-tracing layer grew the router new per-request hot
    functions: ``_dispatch()`` (the worker loop that stamps
    route_selected/connect/sent/reply/completed and the terminal
    shed/failed spans), ``_route_with_waits()`` (the candidate-wait
    table every route decision records), ``_observe_completion()`` (the
    span-ring/window fold that runs once per terminal request), and
    ``router_beat()`` (the kind=router heartbeat snapshot). Every value
    they touch is host-side by construction — monotonic clock stamps,
    parsed heartbeat JSON, the router's own counters — and the whole
    point of the ≤100µs per-request stamp budget is that OBSERVING a
    request must not slow it: a ``device_get`` / ``block_until_ready``
    / ``.item()`` / device-``float()`` in any of these would serialize
    every request in the fleet behind a pipeline drain, turning the
    telemetry into the regression it exists to catch. Deliberately
    DISJOINT from SAV118's set (admit/route/note_result/_refresh_views/
    drain/resume) — same module, different surface, so a finding names
    the layer that actually regressed.
    """

    id = "SAV119"
    name = "router-trace-hot-path-sync"
    severity = "error"
    hint = (
        "keep the router's tracing surface host-only (stamps are "
        "monotonic clock reads; the span ring and windows hold plain "
        "floats — no device value belongs in reach); if a sync here "
        "is truly intentional, pragma it with a justification"
    )

    # The router's per-request trace surface. Deliberately DISJOINT
    # from SAV101's HOT_FUNCTIONS and the SAV111/SAV112/SAV115/SAV116/
    # SAV118 sets (overlap would double-report the same call).
    TRACE_FUNCTIONS = frozenset(
        {"_dispatch", "_route_with_waits", "_observe_completion",
         "router_beat"}
    )

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.TRACE_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="router trace hot path",
                    coda="observing a request must not slow it",
                )


# ---------------------------------------------------------------- SAV117


class AdhocPartitionSpec(Rule):
    """``PartitionSpec``/``NamedSharding`` constructed outside the layout
    module.

    :class:`sav_tpu.parallel.layout.SpecLayout` is the single source of
    truth for every param/activation spec in the repo (ISSUE 13): the
    trainer, the serve engine, and the tools place tensors through the
    layout's derived shardings (``BoundLayout.param_shardings`` /
    ``batch_sharding``) or the :mod:`sav_tpu.parallel.mesh` helpers. An
    inline ``P(...)`` or ``NamedSharding(...)`` anywhere else forks that
    source of truth — the spec it states is invisible to the layout's
    golden snapshots, to ``tools/mesh_tune.py``'s search space, and to
    the ``notes.layout`` provenance stamp, so a layout change silently
    stops covering it. Scoped to everything OUTSIDE ``sav_tpu/parallel/``
    (the layout subsystem and the collective ops that implement it are
    where specs legitimately originate).
    """

    id = "SAV117"
    name = "adhoc-partition-spec"
    severity = "warning"
    hint = (
        "derive the sharding from the layout (BoundLayout.param_shardings"
        "/batch_sharding) or the sav_tpu.parallel.mesh helpers "
        "(batch_sharding/batch_sharding_at/replicated) instead of "
        "constructing PartitionSpec/NamedSharding inline"
    )

    LAYOUT_PATHS = ("sav_tpu/parallel/",)
    CTORS = {
        "jax.sharding.PartitionSpec": "PartitionSpec",
        "jax.sharding.NamedSharding": "NamedSharding",
    }

    def check(self, module):
        if module.relpath.startswith(self.LAYOUT_PATHS):
            return
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            resolved = module.resolve_call(node)
            if resolved in self.CTORS:
                yield _finding(
                    self,
                    node,
                    f"ad-hoc {self.CTORS[resolved]}() outside "
                    "sav_tpu/parallel/ forks the SpecLayout source of "
                    "truth",
                )


# ---------------------------------------------------------------- SAV120


class UnscaledInt8Cast(Rule):
    """Raw int8 cast outside the quantization module.

    ``sav_tpu/ops/quant.py`` is the single source of int8 truth (ISSUE
    17): every int8 tensor in the repo is born next to a per-channel
    scale (``quantize_channelwise`` / ``quantize_stochastic``) so that
    ``q * scale ≈ a`` always holds and the int32-accumulating dot can
    dequantize on exit. A bare ``x.astype(jnp.int8)`` or
    ``jnp.asarray(x, jnp.int8)`` anywhere else in the model/op/serve
    stack produces an int8 tensor with NO scale: values outside
    [-128, 127] wrap silently, fractional values truncate, and the
    result still *type-checks* into every quantized dot — the numeric
    corruption only surfaces as an accuracy drift long after the cast.
    Scoped to ``sav_tpu/ops|models|serve`` (the layers quantized
    tensors flow through); ``quant.py`` itself is exempt — scaled casts
    are its whole job.
    """

    id = "SAV120"
    name = "unscaled-int8-cast"
    severity = "error"
    hint = (
        "go through sav_tpu.ops.quant (quantize_channelwise / "
        "quantize_stochastic / quantize_params) so the int8 tensor "
        "carries its per-channel scale; if an unscaled cast is truly "
        "intentional, pragma it with a justification"
    )

    SCOPE = ("sav_tpu/ops/", "sav_tpu/models/", "sav_tpu/serve/")
    EXEMPT = ("sav_tpu/ops/quant.py",)
    INT8_DTYPES = frozenset({"jax.numpy.int8", "numpy.int8"})
    ARRAY_CTORS = frozenset(
        {
            "jax.numpy.asarray", "jax.numpy.array", "jax.numpy.full",
            "jax.numpy.zeros", "jax.numpy.ones", "numpy.asarray",
            "numpy.array",
        }
    )

    def _is_int8(self, module, node) -> bool:
        if isinstance(node, ast.Constant) and node.value == "int8":
            return True
        return module.resolve(node) in self.INT8_DTYPES

    def check(self, module):
        if (
            not module.relpath.startswith(self.SCOPE)
            or module.relpath in self.EXEMPT
        ):
            return
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            dtype_nodes = [
                kw.value for kw in node.keywords if kw.arg == "dtype"
            ]
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
            ):
                dtype_nodes += node.args[:1]
                what = ".astype(int8)"
            elif module.resolve_call(node) in self.ARRAY_CTORS:
                # asarray/array take dtype positionally second; the
                # zeros/ones/full family keyword-only in this repo's
                # idiom (positional shapes) — the dtype kwarg covers it.
                dtype_nodes += node.args[1:2]
                what = f"{node.func.attr}(..., int8)"
            else:
                continue
            if any(self._is_int8(module, d) for d in dtype_nodes):
                yield _finding(
                    self,
                    node,
                    f"unscaled int8 cast ({what}) outside "
                    "sav_tpu/ops/quant.py — an int8 tensor with no "
                    "per-channel scale wraps/truncates silently",
                )


# ---------------------------------------------------------------- SAV125


def _attr_chain(node) -> list:
    """Lowercased name parts along an attribute chain, root first:
    ``self.alerts.observe`` -> ``["self", "alerts", "observe"]``."""
    parts: list = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr.lower())
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id.lower())
    parts.reverse()
    return parts


class AlertEvalInHotPath(Rule):
    """Alert evaluation / rollup writes inside request hot paths.

    The fleet metrics pipeline runs at heartbeat cadence by design:
    ``serve_beat()`` evaluates the alert rules once per beat, the
    router's heartbeat thread (``_hb_loop`` -> ``_roll_tick``) advances
    the rollup ladder once per interval, and the bench parent flushes
    once post-run — so the pipeline's cost is O(rules + new bytes) per
    *beat*, never per request. Calling ``AlertEngine.observe()`` /
    ``AlertRule.evaluate()`` or ``Roller.roll_once()/flush()`` from the
    batcher's submit path, the per-batch telemetry stamps, or the
    router's admission/dispatch surface would put rule evaluation, JSON
    encoding, and file appends on the request latency path — the
    observability regressing the p99 it exists to guard. The scope
    deliberately overlaps the SAV115/SAV116/SAV118/SAV119 function sets
    (same hot paths) but reports DIFFERENT calls (pipeline writes, not
    device syncs), so nothing double-reports.
    """

    id = "SAV125"
    name = "alert-eval-in-hot-path"
    severity = "error"
    hint = (
        "alert rules and rollups belong at heartbeat cadence: evaluate "
        "in serve_beat()/the router heartbeat thread (or post-run), "
        "never in submit/dispatch/per-batch stamp paths; if a hot-path "
        "evaluation is truly intentional, pragma it with a "
        "justification"
    )

    # The request hot paths: the batcher's submit/forming surface, the
    # per-batch telemetry stamps, and the router's admission/dispatch
    # functions. serve_beat/_hb_loop/_roll_tick/router_beat are the
    # sanctioned cadenced homes and are deliberately NOT in scope.
    FUNCTIONS = frozenset({
        # batcher (SAV115's set)
        "submit", "submit_raw", "next_batch", "_formed_batches",
        "_place_formed",
        # per-batch telemetry stamps (SAV116's set, minus serve_beat)
        "stamp", "begin_trace", "observe_window", "observe_completed",
        "observe_shed",
        # router request surface (SAV118 + SAV119's sets, minus
        # router_beat)
        "admit", "route", "note_result", "_refresh_views",
        "_dispatch", "_route_with_waits", "_observe_completion",
    })

    _ALERT_METHODS = frozenset({"observe", "evaluate"})
    _ROLL_METHODS = frozenset({"roll_once", "roll", "flush"})

    def check(self, module):
        for fn in module.functions:
            if fn.name not in self.FUNCTIONS:
                continue
            for node in _walk_excluding_nested(fn):
                if not isinstance(node, ast.Call):
                    continue
                resolved = module.resolve_call(node) or ""
                if resolved.startswith(
                    ("sav_tpu.obs.alerts.", "sav_tpu.obs.rollup.")
                ):
                    yield _finding(
                        self,
                        node,
                        f"{resolved}() in request hot path {fn.name}() — "
                        "the metrics pipeline runs at heartbeat cadence, "
                        "not per request",
                    )
                    continue
                if not isinstance(node.func, ast.Attribute):
                    continue
                chain = _attr_chain(node.func)
                attr = node.func.attr
                if attr in self._ALERT_METHODS and any(
                    "alert" in part for part in chain[:-1]
                ):
                    yield _finding(
                        self,
                        node,
                        f"alert evaluation (.{attr}() on "
                        f"{'.'.join(chain[:-1])}) in request hot path "
                        f"{fn.name}() — rules evaluate once per beat in "
                        "serve_beat(), not per request",
                    )
                elif attr in self._ROLL_METHODS and any(
                    "roll" in part for part in chain[:-1]
                ):
                    yield _finding(
                        self,
                        node,
                        f"rollup write (.{attr}() on "
                        f"{'.'.join(chain[:-1])}) in request hot path "
                        f"{fn.name}() — the ladder advances on the "
                        "router's heartbeat thread, not per request",
                    )


# ---------------------------------------------------------------- SAV126


class QualityEvalInHotPath(Rule):
    """Prediction-quality evaluation inside request hot paths.

    The quality layer's contract (sav_tpu/serve/quality.py,
    sav_tpu/obs/quality.py, docs/quality.md) is that measuring
    prediction quality adds ZERO device syncs and zero per-request
    eval to the serving path: the output digests are traced INTO the
    serving executable and ride the device loop's one sanctioned
    result fetch; the windowed folds/drift gates run on values that
    are already host-side; probes run on their own low-cadence thread;
    shadow scoring runs on the router's dedicated shadow worker (the
    dispatch path only does an O(1) bounded queue put). Two ways an
    edit silently breaks that, and this rule owns both:

    1. A device sync slipped into the quality fold functions
       themselves (``observe_digests`` / ``score_shadow`` /
       ``quality_snapshot`` / ``observe_probe`` — outside every other
       sync rule's scope, so SAV126 audits them with the shared
       ``_metrics_sync_findings`` catalogue). ``observe_probe`` may
       block on request FUTURES by design — it never runs on the hot
       path — but a raw ``device_get``/``.item()`` there would still
       be a smell the catalogue rightly flags.
    2. A quality evaluation called FROM a request hot path — a
       ``sav_tpu.{obs,serve}.quality`` call, or a
       snapshot/score/digest method on a quality/probe/shadow/scorer
       object, inside the batcher submit path, the per-batch telemetry
       stamps, or the router admission/dispatch surface. Windowed
       churn/PSI folds and logit comparisons are O(window·classes)
       host math: cheap at heartbeat cadence, poison at request rate.
       The scope deliberately overlaps SAV125's hot-path set (same
       functions) but reports DIFFERENT calls (quality evals, not
       alert/rollup writes), so nothing double-reports. The engine's
       ``_complete`` is deliberately NOT in scope: its
       ``observe_digests`` fold on the already-fetched host digests is
       the sanctioned per-batch fold, like the latency ledger's.
    """

    id = "SAV126"
    name = "quality-eval-in-hot-path"
    severity = "error"
    hint = (
        "quality folds belong off the request path: digests ride the "
        "device loop's existing fetch, probes run on the probe thread, "
        "shadow scoring on the shadow worker, snapshots at heartbeat "
        "cadence (serve_beat/_quality_tick); if a hot-path evaluation "
        "is truly intentional, pragma it with a justification"
    )

    # The quality layer's own surface: audited host-only by the shared
    # sync catalogue. Disjoint from SAV111/SAV112/SAV115/SAV116/
    # SAV118/SAV119's sets — overlapping scopes would double-report.
    QUALITY_FUNCTIONS = frozenset({
        "observe_digests", "observe_probe", "score_shadow",
        "quality_snapshot",
    })

    # The request hot paths (SAV125's set — same paths, different
    # calls). _complete and the heartbeat/shadow-worker homes are
    # deliberately absent.
    FUNCTIONS = AlertEvalInHotPath.FUNCTIONS

    _EVAL_METHODS = frozenset({
        "observe_digests", "observe_probe", "score_shadow",
        "quality_snapshot", "snapshot", "score",
    })
    _QUALITY_ROOTS = ("quality", "probe", "shadow", "scorer")

    def check(self, module):
        for fn in module.functions:
            if fn.name in self.QUALITY_FUNCTIONS:
                yield from _metrics_sync_findings(
                    self, module, fn,
                    where="quality fold",
                    coda="digests ride the device loop's existing fetch",
                )
            if fn.name not in self.FUNCTIONS:
                continue
            for node in _walk_excluding_nested(fn):
                if not isinstance(node, ast.Call):
                    continue
                resolved = module.resolve_call(node) or ""
                if resolved.startswith(
                    ("sav_tpu.obs.quality.", "sav_tpu.serve.quality.")
                ):
                    yield _finding(
                        self,
                        node,
                        f"{resolved}() in request hot path {fn.name}() — "
                        "quality evaluation runs at heartbeat/probe "
                        "cadence, not per request",
                    )
                    continue
                if not isinstance(node.func, ast.Attribute):
                    continue
                chain = _attr_chain(node.func)
                attr = node.func.attr
                if attr in self._EVAL_METHODS and any(
                    root in part
                    for part in chain[:-1]
                    for root in self._QUALITY_ROOTS
                ):
                    yield _finding(
                        self,
                        node,
                        f"quality evaluation (.{attr}() on "
                        f"{'.'.join(chain[:-1])}) in request hot path "
                        f"{fn.name}() — fold/score off the request path "
                        "(heartbeat, probe thread, or shadow worker)",
                    )


# ----------------------------------------------------------- SAV100 (meta)


class _PragmaHygiene(Rule):
    """Suppressions must name real rules and record a justification.

    A ``# savlint: disable=...`` with no ``-- reason`` (or an unknown
    rule id) defeats the audit trail the pragma system exists for; this
    meta-rule makes such pragmas findings themselves, and cannot be
    pragma'd away.
    """

    id = "SAV100"
    name = "pragma-hygiene"
    severity = "error"
    hint = "write '# savlint: disable=<RULE-ID> -- one-line justification'"


_PRAGMA_HYGIENE = _PragmaHygiene()


def check_pragma_hygiene(module: ModuleInfo) -> list[Finding]:
    findings = []
    known = {r.id for r in ALL_RULES} | {"SAV001"}
    for p in module.pragmas:
        unknown = sorted(p.rules - known)
        if unknown:
            findings.append(
                _finding(
                    _PRAGMA_HYGIENE,
                    type("L", (), {"lineno": p.line, "col_offset": 0,
                                   "end_lineno": p.line})(),
                    f"pragma names unknown rule(s) {', '.join(unknown)}",
                    code=module.function_source_line(p.line),
                )
            )
        if not p.justification:
            findings.append(
                _finding(
                    _PRAGMA_HYGIENE,
                    type("L", (), {"lineno": p.line, "col_offset": 0,
                                   "end_lineno": p.line})(),
                    "pragma has no justification — every suppression must "
                    "say why the violation is intentional",
                    code=module.function_source_line(p.line),
                )
            )
    return findings


ALL_RULES = [
    HostSyncInHotLoop(),
    JitWithoutDonation(),
    PrngKeyReuse(),
    PythonScalarArgRetrace(),
    TimeInJit(),
    InlineDevicePutInFit(),
    UnlockedThreadSharedState(),
    F32LiteralPromotion(),
    JitInLoop(),
    AdhocSeedDerivation(),
    RecorderHotLoopSync(),
    FleetHotPathSync(),
    ProfilerInHotPath(),
    BareExitInLibrary(),
    ServeHotLoopSync(),
    ServeTelemetryHotPathSync(),
    AdhocPartitionSpec(),
    RouterHotPathSync(),
    RouterTraceHotPathSync(),
    UnscaledInt8Cast(),
    AlertEvalInHotPath(),
    QualityEvalInHotPath(),
]

# The whole-program concurrency pass (SAV121–SAV124) lives in its own
# module — it is the one ProjectRule family and carries the shared
# lockset/lock-graph analysis tools/lockgraph.py also imports.
from sav_tpu.analysis.concurrency import CONCURRENCY_RULES  # noqa: E402

ALL_RULES = ALL_RULES + CONCURRENCY_RULES


def rule_catalog() -> list[dict]:
    """Machine-readable rule table (CLI --list-rules, docs generation)."""
    catalog = [
        {
            "id": r.id,
            "name": r.name,
            "severity": r.severity,
            "summary": (r.__doc__ or "").strip().splitlines()[0],
            "hint": r.hint,
        }
        for r in [_PRAGMA_HYGIENE] + ALL_RULES
    ]
    return catalog
