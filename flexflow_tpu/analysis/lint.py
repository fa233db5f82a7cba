"""fflint: AST rules for the JAX hazards this codebase keeps re-fixing.

Every rule encodes a bug class a past PR fixed by hand:

- `host_sync_in_loop` — `jax.device_get` (a full device drain) inside a
  `for`/`while` loop. The r09 pipelined engine existed to remove exactly
  this from the step loop; new ones must not creep back in. Fetches
  behind a telemetry/diagnostics gate are exempt (the gate IS the fix),
  including gates bound to a local (`need_losses = tel is not None`).
- `unsorted_dict_hash` — a `for` loop over `.items()`/`.keys()`/
  `.values()` (not wrapped in `sorted(...)`) inside a fingerprint/hash
  function. Dict order is insertion order, so two processes that learned
  entries in different orders hash differently — a warm-start cache that
  misses across restarts for no reason (warmstart/fingerprint.py is
  keyed content-addressing; it must be order-free).
- `global_rng` — module-level `np.random.*` / stdlib `random.*` calls
  (not RandomState/default_rng instances). The r06 resilience PR
  replaced a global-RNG shuffle because it made resume non-replayable.
- `time_in_trace` — `time.*` / RNG calls inside a TRACED function (jit
  decorator, or passed to jit / shard_map / pallas_call / lax control
  flow). These execute once at trace time and bake a constant into the
  executable — the classic "why is my timestamp frozen" bug.
- `coordinator_collective` — a collective (barrier / broadcast_json /
  sync_global_devices / psum...) inside an `is_coordinator()` /
  `process_index() == 0` branch: the other processes never reach the
  collective, so the fleet deadlocks. The correct idiom is
  `broadcast_json(payload if is_coordinator() else None)` — gate the
  PAYLOAD, not the collective.
- `donated_reuse` — a buffer passed at a donated argnum of a known step
  executable (train step / chunked scan / decode step) and then read
  host-side without being rebound by the call's own assignment: the
  donated buffer is dead after the call on backends that honor donation.
- `low_precision_accum` — a summing reduction (`jnp.sum`/`mean`/
  `prod`/`cumsum`/`logsumexp`/`einsum`) whose argument is explicitly
  cast to bf16/fp16 (or whose `dtype=` pins a low-precision
  accumulator). Long low-precision sums drift (Micikevicius et al.,
  PAPERS.md "Numerics"); the codebase's convention is f32 accumulation
  with one final downcast (loss.py, ops/core.py) — the ffsan dtype-flow
  pass checks the same invariant at the graph level.
- `host_divergent_branch` — an `if` whose test calls a per-host-
  nondeterministic source (time.*, RNG, os.environ/getenv,
  socket.gethostname) guarding a collective (deadlock: some hosts never
  arrive — error) or a trace-entry call (hosts compile divergent
  executables — warning). The r13 multihost pricing divergence
  generalized: gate on a BROADCAST value, never a locally measured one.
- `unverified_transition` — a direct call to one of the state
  re-placement appliers (`place_update_sharded`, `place_like`,
  `restore_tree`) in a function that never consults the fftrans
  transition checker (analysis/transition.py). Re-placing live/restored
  state outside the checker-gated path is exactly how a dropped
  mapping, dtype drift, or a stage-3 shard without a gather path
  becomes a shape crash or silent corruption mid-restore — route
  through `migrate_state` / `verify_restore_transition` (a fresh-init
  placement at compile is not a transition: pragma it).
- `raw_timer_in_hot_path` — two or more bare `time.perf_counter()` /
  `time.time()` reads (a start/stop pair) inside a step/decode/prefill
  hot-path function outside `telemetry/`. A hand-rolled timer pair is a
  measurement the ffpulse metrics plane never sees — route it through
  `telemetry.span(...)` or `telemetry.observe(...)` so it lands in the
  mergeable histograms, or gate it behind a telemetry check. Sites
  where the raw read IS the product (the device-sync timing the span
  wraps, wall-clock pacing) carry the pragma.
- `unverified_rule_load` — a call that constructs or loads
  `GraphXfer`s (`load_rule_collection` without the verifying `config=`
  argument, `compile_pattern_rule`, `generate_all_pcg_xfers`) in a
  function that never consults the ffrules verifier
  (analysis/rules.py). Rules injected into the search unverified are
  exactly how an unsound rewrite becomes a silently-wrong plan — the
  r19 twin of `unverified_transition`; the built-in registry's own
  load sites are pragma'd because scripts/ffrules.py sweeps the full
  generated registry in CI.
- `unnamed_op_scope` — an op-dispatch call (`*.op_def.forward` /
  `*.op_def.backward`) in executor.py or ops/ with no lexically
  enclosing `jax.named_scope(...)` block. The ffscope profiling plane
  attributes trace events back to PCG nodes purely by named_scope
  labels (scope/attribution.py) — a dispatch outside a scope produces
  device time the attribution can only file as `unattributed_s`, so
  the fidelity table silently loses that op. Dispatches that run under
  a CALLER's named_scope (runtime nesting the AST cannot see, e.g. the
  stage-3 remat closure invoked from the scoped forward loop) carry
  the pragma.

Suppression: a trailing `# fflint: ok` (optionally naming codes,
`# fflint: ok host_sync_in_loop`) on the flagged line or its enclosing
`def` line. Used where the hazard is the point (calibration timing
loops fetch inside a loop BY DESIGN).

`scripts/fflint.py` is the CLI; the ffcheck pass pipeline reuses
`coordinator_collective` + `donated_reuse` as its source-level checks.
"""

from __future__ import annotations

import ast
import os

from .findings import Finding, SEV_ERROR, SEV_WARNING

PASS_NAME = "fflint"

ALL_RULES = ("host_sync_in_loop", "unsorted_dict_hash", "global_rng",
             "time_in_trace", "coordinator_collective", "donated_reuse",
             "low_precision_accum", "host_divergent_branch",
             "unverified_transition", "unverified_rule_load",
             "raw_timer_in_hot_path", "unnamed_op_scope")

# identifiers whose presence in an `if` test marks the branch as a
# telemetry/diagnostics gate (a gated fetch is the sanctioned pattern)
_GATE_IDS = ("tel", "telemetry", "diag", "diagnostics", "sampled",
             "verbose", "profiling", "debug")

_TIME_FUNCS = {"time", "perf_counter", "monotonic", "process_time",
               "time_ns", "perf_counter_ns", "monotonic_ns"}
_NP_RANDOM_OK = {"RandomState", "default_rng", "Generator",
                 "SeedSequence", "PCG64", "Philox", "MT19937"}
_PY_RANDOM_FUNCS = {"random", "randint", "choice", "choices", "shuffle",
                    "seed", "uniform", "randrange", "sample", "gauss",
                    "betavariate", "getrandbits"}
_COLLECTIVES = {"barrier", "broadcast_json", "sync_global_devices",
                "broadcast_one_to_all", "psum", "pmean", "pmax",
                "all_gather", "all_to_all", "ppermute",
                "process_allgather"}
_TRACE_ENTRY = {"jit", "scan", "fori_loop", "while_loop", "cond",
                "switch", "associative_scan", "shard_map", "pallas_call",
                "checkpoint", "remat", "vmap", "pmap", "grad",
                "value_and_grad"}

# donated-step callees (by last identifier) → donated argnums. MUST
# match the executor's _donate_argnums declarations — the ffcheck
# donation pass cross-checks this registry against executor.py's AST
# (analysis/donation.py), so the two cannot drift silently.
DONATED_CALLEES = {
    "step_fn": (0, 1, 2, 3, 4),       # build_train_step
    "_train_step": (0, 1, 2, 3, 4),
    "chunk_fn": (0, 1, 2, 3, 4),      # build_chunked_train_step
    "eval_fn": (2,),                  # build_eval_step
    "_eval_step": (2,),
    "_step_fn": (1,),                 # build_decode_step (KV-cache state)
    "_decode_step": (1,),
    "_verify_fn": (1,),               # build_verify_step (speculative)
    "_verify_step": (1,),
    "_copy_fn": (0,),                 # build_block_copy (paged KV pools)
    "_copy_fn_w": (0,),               # the window group's (serving/paged.py)
    "_inject_fn": (0,),               # build_kv_inject (disagg handoff)
    "_gather_fn": (0,),               # build_param_gather (stage-3 tree)
    "gather_fn": (0,),
}

_HASH_FN_HINTS = ("fingerprint", "signature", "digest", "_sha", "hash")

# state re-placement appliers (the reshard-apply surface) and the
# fftrans checker entry points that gate them (analysis/transition.py,
# resilience/migrate.py) — a function calling an applier must also
# consult a checker, or the re-placement runs unverified
_TRANSITION_APPLIERS = {"place_update_sharded", "place_like",
                        "restore_tree"}
_TRANSITION_CHECKERS = {"verify_restore_transition", "verify_transition",
                        "gate_transition", "build_transition_plan",
                        "plan_model_transition", "migrate_state"}

# GraphXfer construct/load surface (search/substitution.py) and the
# ffrules checker entry points that gate it (analysis/rules.py) — a
# function loading rules must also consult the verifier, or pass
# config= to load_rule_collection (the loader then verifies internally)
_RULE_LOADERS = {"load_rule_collection", "compile_pattern_rule",
                 "generate_all_pcg_xfers"}
_RULE_CHECKERS = {"verify_rule", "verify_rules", "verify_registry",
                  "gate_loaded_rules", "RuleVerificationError"}

# summing reductions the low-precision-accumulation rule watches
# (order statistics — max/min/argmax — carry no accumulation error)
_SUM_FUNCS = {"sum", "mean", "prod", "cumsum", "logsumexp", "einsum"}

# hot-path function name hints for the raw-timer rule — the per-step /
# per-token functions whose measurements belong in the metrics plane
_HOT_PATH_HINTS = ("step", "decode", "prefill")
# bare-name timer calls (`from time import perf_counter` idiom); the
# dotted `time.X` forms reuse _TIME_FUNCS
_BARE_TIMER_NAMES = {"perf_counter", "monotonic", "perf_counter_ns",
                     "monotonic_ns"}


def _dotted(node) -> str:
    """Name/Attribute chain → dotted string ('' when not a pure chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _last_ident(node) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


class _FileLint:
    def __init__(self, src: str, path: str, select):
        self.tree = ast.parse(src)
        self.lines = src.splitlines()
        self.path = path
        self.select = set(select) if select else set(ALL_RULES)
        self.findings: list[Finding] = []
        self._parent_map = None  # built lazily (one full-tree walk)

    @property
    def _parents(self) -> dict:
        if self._parent_map is None:
            self._parent_map = {}
            for parent in ast.walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self._parent_map[id(child)] = parent
        return self._parent_map

    # ------------------------------------------------------------ pragmas

    def _suppressed(self, node, code: str) -> bool:
        for ln in {getattr(node, "lineno", 0), self._def_line(node)}:
            if not (0 < ln <= len(self.lines)):
                continue
            line = self.lines[ln - 1]
            if "# fflint: ok" not in line:
                continue
            tail = line.split("# fflint: ok", 1)[1].strip()
            listed = [t.strip(",") for t in tail.split()
                      if t.strip(",") in ALL_RULES]
            if not listed or code in listed:
                return True
        return False

    def _def_line(self, node) -> int:
        cur = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur.lineno
            cur = self._parents.get(id(cur))
        return 0

    def _emit(self, node, severity, code, message, **details):
        if code not in self.select or self._suppressed(node, code):
            return
        self.findings.append(Finding(
            severity, code, message, pass_name=PASS_NAME,
            where=f"{self.path}:{getattr(node, 'lineno', 0)}",
            details=details or {}))

    # --------------------------------------------------------- rule: sync

    def _gate_names(self, fn) -> set:
        """Gate identifiers for one function: the builtin set plus any
        local assigned FROM a gated expression (need_losses = tel is not
        None)."""
        gates = set(_GATE_IDS)
        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                tgt = node.targets[0].id
                if tgt in gates:
                    continue
                idents = {n.id for n in ast.walk(node.value)
                          if isinstance(n, ast.Name)}
                idents |= {n.attr for n in ast.walk(node.value)
                           if isinstance(n, ast.Attribute)}
                if any(any(g in i for g in gates) for i in idents):
                    gates.add(tgt)
                    changed = True
        return gates

    def _mentions_gate(self, test, gates) -> bool:
        for n in ast.walk(test):
            ident = ""
            if isinstance(n, ast.Name):
                ident = n.id
            elif isinstance(n, ast.Attribute):
                ident = n.attr
            if ident and any(g in ident for g in gates):
                return True
        return False

    def rule_host_sync_in_loop(self):
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            gates = self._gate_names(fn)
            self._scan_sync(fn.body, gates, in_loop=False, gated=False)

    def _scan_sync(self, stmts, gates, in_loop, gated):
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs get their own pass
            if isinstance(node, (ast.For, ast.While)):
                self._scan_sync(node.body, gates, True, gated)
                self._scan_sync(node.orelse, gates, in_loop, gated)
                continue
            if isinstance(node, ast.If):
                g = gated or self._mentions_gate(node.test, gates)
                self._scan_sync(node.body, gates, in_loop, g)
                self._scan_sync(node.orelse, gates, in_loop, g)
                continue
            if isinstance(node, ast.With):
                self._scan_sync(node.body, gates, in_loop, gated)
                continue
            if isinstance(node, ast.Try):
                for sub in (node.body, node.orelse, node.finalbody):
                    self._scan_sync(sub, gates, in_loop, gated)
                for h in node.handlers:
                    self._scan_sync(h.body, gates, in_loop, gated)
                continue
            if not in_loop:
                continue
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                if _last_ident(call.func) != "device_get":
                    continue
                g = gated
                # conditional-expression gate: x if need_losses else None
                cur = call
                while cur is not None and not g:
                    if isinstance(cur, ast.IfExp) and \
                            self._mentions_gate(cur.test, gates):
                        g = True
                    cur = self._parents.get(id(cur))
                    if isinstance(cur, ast.stmt):
                        break
                if g:
                    continue
                self._emit(
                    call, SEV_WARNING, "host_sync_in_loop",
                    "jax.device_get inside a loop is a per-iteration "
                    "device drain — hoist it out, batch it per chunk, or "
                    "gate it behind telemetry/diagnostics")

    # --------------------------------------------------- rule: dict hash

    def rule_unsorted_dict_hash(self):
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            hashy = any(h in fn.name.lower() for h in _HASH_FN_HINTS)
            if not hashy:
                for call in ast.walk(fn):
                    if isinstance(call, ast.Call):
                        d = _dotted(call.func)
                        if d.startswith("hashlib.") or \
                                _last_ident(call.func) == "_sha":
                            hashy = True
                            break
            if not hashy:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.For):
                    continue
                it = node.iter
                if isinstance(it, ast.Call) and \
                        isinstance(it.func, ast.Attribute) and \
                        it.func.attr in ("items", "keys", "values"):
                    self._emit(
                        node, SEV_WARNING, "unsorted_dict_hash",
                        f"iteration over .{it.func.attr}() inside hash "
                        f"function {fn.name}(): dict order is insertion "
                        f"order — wrap in sorted(...) so the digest is "
                        f"order-free")

    # --------------------------------------------------- rule: global rng

    def _rng_call(self, call) -> str:
        d = _dotted(call.func)
        parts = d.split(".")
        if len(parts) >= 3 and parts[-3] in ("np", "numpy") \
                and parts[-2] == "random" and \
                parts[-1] not in _NP_RANDOM_OK:
            return d
        if len(parts) == 2 and parts[0] == "random" \
                and parts[1] in _PY_RANDOM_FUNCS:
            return d
        return ""

    def rule_global_rng(self):
        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            d = self._rng_call(call)
            if d:
                self._emit(
                    call, SEV_WARNING, "global_rng",
                    f"{d}() uses the process-global RNG — seed-keyed "
                    f"np.random.RandomState / default_rng keeps resume "
                    f"and multi-process runs replayable")

    # ------------------------------------------------- rule: time in jit

    def _traced_defs(self) -> set:
        """ids of FunctionDef nodes that are traced: jit-decorated, or
        referenced (possibly through functools.partial) as an argument
        of a trace-entry call (jit/shard_map/pallas_call/lax control
        flow) — plus every def nested inside one."""
        defs_by_name: dict[str, list] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)
        marked: set[int] = set()

        def mark_name(name: str):
            for d in defs_by_name.get(name, []):
                marked.add(id(d))

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    tgt = dec.func if isinstance(dec, ast.Call) else dec
                    if _last_ident(tgt) in ("jit", "partial"):
                        if _last_ident(tgt) == "partial" and isinstance(
                                dec, ast.Call):
                            if not any(_last_ident(a) == "jit"
                                       for a in dec.args):
                                continue
                        marked.add(id(node))
            if not isinstance(node, ast.Call):
                continue
            if _last_ident(node.func) not in _TRACE_ENTRY:
                continue
            cands = list(node.args) + [k.value for k in node.keywords]
            for a in cands:
                if isinstance(a, ast.Call) and \
                        _last_ident(a.func) == "partial" and a.args:
                    a = a.args[0]
                if isinstance(a, (ast.Name, ast.Attribute)):
                    nm = _last_ident(a)
                    if nm:
                        mark_name(nm)
        # nested defs inside a traced def trace with it
        out = set(marked)
        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.tree):
                if not isinstance(node,
                                  (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if id(node) not in out:
                    continue
                for sub in ast.walk(node):
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) \
                            and id(sub) not in out:
                        out.add(id(sub))
                        changed = True
        return out

    def rule_time_in_trace(self):
        traced = self._traced_defs()
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if id(fn) not in traced:
                continue
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call):
                    continue
                d = _dotted(call.func)
                parts = d.split(".")
                bad = ""
                if len(parts) == 2 and parts[0] == "time" \
                        and parts[1] in _TIME_FUNCS:
                    bad = d
                elif d in ("datetime.now", "datetime.datetime.now",
                           "datetime.utcnow"):
                    bad = d
                elif self._rng_call(call):
                    bad = self._rng_call(call)
                if bad:
                    self._emit(
                        call, SEV_ERROR, "time_in_trace",
                        f"{bad}() inside traced function {fn.name}() "
                        f"executes ONCE at trace time and bakes a "
                        f"constant into the executable")

    # ------------------------------------- rule: coordinator collective

    def _is_coordinator_test(self, test) -> tuple[bool, bool]:
        """(gates_body, gates_orelse): does this `if` test make one
        branch coordinator-only? Handles `is_coordinator()`,
        `process_index() == 0`, and their negations."""
        neg = False
        inner = test
        while isinstance(inner, ast.UnaryOp) and \
                isinstance(inner.op, ast.Not):
            neg = not neg
            inner = inner.operand
        coord = False
        for n in ast.walk(inner):
            if isinstance(n, ast.Call) and \
                    _last_ident(n.func) == "is_coordinator":
                coord = True
            if isinstance(n, ast.Compare) and \
                    isinstance(n.left, ast.Call) and \
                    _last_ident(n.left.func) == "process_index":
                coord = True
        if not coord:
            return False, False
        return (not neg, neg)

    def rule_coordinator_collective(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.If):
                continue
            body_coord, orelse_coord = self._is_coordinator_test(node.test)
            for stmts, flagged in ((node.body, body_coord),
                                   (node.orelse, orelse_coord)):
                if not flagged:
                    continue
                for sub in stmts:
                    for call in ast.walk(sub):
                        if isinstance(call, ast.Call) and \
                                _last_ident(call.func) in _COLLECTIVES:
                            self._emit(
                                call, SEV_ERROR, "coordinator_collective",
                                f"collective "
                                f"{_last_ident(call.func)}() inside a "
                                f"coordinator-only branch: the other "
                                f"processes never reach it — multihost "
                                f"deadlock. Gate the PAYLOAD, not the "
                                f"collective (broadcast_json(x if "
                                f"is_coordinator() else None))")

    # ------------------------------------------- rule: donated reuse

    def rule_donated_reuse(self):
        # one cheap pre-scan: most files (and most functions) never call
        # a donated executable — only collect per-function load/store
        # events where a donated call actually appears
        calls = [n for n in ast.walk(self.tree)
                 if isinstance(n, ast.Call)
                 and _last_ident(n.func) in DONATED_CALLEES]
        if not calls:
            return
        involved: dict[int, ast.AST] = {}
        for c in calls:
            cur = self._parents.get(id(c))
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cur = self._parents.get(id(cur))
            if cur is not None:
                involved.setdefault(id(cur), cur)
        for fn in involved.values():
            events = []  # (lineno, col, kind, expr string)
            for node in ast.walk(fn):
                d = ""
                if isinstance(node, (ast.Name, ast.Attribute)):
                    d = _dotted(node)
                if not d:
                    continue
                kind = ("store" if isinstance(
                    getattr(node, "ctx", None), ast.Store) else "load")
                events.append((node.lineno, node.col_offset, kind, d))
            events.sort()
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _last_ident(node.func)
                donated = DONATED_CALLEES.get(callee)
                if donated is None:
                    continue
                stmt = self._enclosing_stmt(node)
                targets: set[str] = set()
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        for n in ast.walk(t):
                            if isinstance(n, (ast.Name, ast.Attribute)):
                                s = _dotted(n)
                                if s:
                                    targets.add(s)
                end = getattr(stmt, "end_lineno", node.lineno)
                for argnum in donated:
                    if argnum >= len(node.args):
                        continue
                    arg = node.args[argnum]
                    if not isinstance(arg, (ast.Name, ast.Attribute)):
                        continue
                    expr = _dotted(arg)
                    if not expr or expr in targets:
                        continue
                    nxt = next(
                        (e for e in events
                         if e[0] > end and e[3] == expr), None)
                    if nxt is not None and nxt[2] == "load":
                        self._emit(
                            node, SEV_ERROR, "donated_reuse",
                            f"{expr} passed at donated argnum {argnum} "
                            f"of {callee}() and read again at line "
                            f"{nxt[0]} without rebinding — the donated "
                            f"buffer is dead after the call",
                            reuse_line=nxt[0], argnum=argnum)

    def _enclosing_stmt(self, node):
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self._parents.get(id(cur))
        return cur

    # --------------------------------------- rule: low-precision accum

    def _low_precision_expr(self, node) -> str:
        """Name of the low-precision dtype an expression subtree pins
        ('' when none): an astype()/dtype= targeting bfloat16/float16."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and \
                    _last_ident(sub.func) == "astype":
                for a in sub.args:
                    d = _dotted(a) or (a.value if isinstance(
                        a, ast.Constant) and isinstance(a.value, str)
                        else "")
                    if isinstance(d, str) and d.split(".")[-1] in (
                            "bfloat16", "float16"):
                        return d
        return ""

    def rule_low_precision_accum(self):
        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            if _last_ident(call.func) not in _SUM_FUNCS:
                continue
            lp = ""
            for kw in call.keywords:
                if kw.arg in ("dtype", "preferred_element_type"):
                    d = _dotted(kw.value)
                    if d.split(".")[-1] in ("bfloat16", "float16"):
                        lp = d
            if not lp:
                for a in call.args:
                    lp = self._low_precision_expr(a)
                    if lp:
                        break
            if lp:
                self._emit(
                    call, SEV_WARNING, "low_precision_accum",
                    f"{_last_ident(call.func)}() accumulates in "
                    f"{lp.split('.')[-1]} — long low-precision sums "
                    f"drift; reduce in f32 and downcast the result "
                    f"(loss.py / ops/core.py convention)")

    # ------------------------------------ rule: host-divergent branch

    def _divergent_source(self, test) -> str:
        """Dotted name of a per-host-nondeterministic call in an `if`
        test ('' when none)."""
        for n in ast.walk(test):
            if not isinstance(n, ast.Call):
                continue
            d = _dotted(n.func)
            parts = d.split(".")
            if len(parts) == 2 and parts[0] == "time" \
                    and parts[1] in _TIME_FUNCS:
                return d
            if self._rng_call(n):
                return d
            if d in ("os.getenv", "os.environ.get",
                     "socket.gethostname", "platform.node"):
                return d
        return ""

    def rule_host_divergent_branch(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.If):
                continue
            src = self._divergent_source(node.test)
            if not src:
                continue
            for stmts in (node.body, node.orelse):
                for sub in stmts:
                    for call in ast.walk(sub):
                        if not isinstance(call, ast.Call):
                            continue
                        callee = _last_ident(call.func)
                        if callee in _COLLECTIVES:
                            self._emit(
                                call, SEV_ERROR,
                                "host_divergent_branch",
                                f"collective {callee}() behind a branch "
                                f"on {src}() — hosts evaluate the test "
                                f"differently and some never reach the "
                                f"collective: fleet deadlock. Decide on "
                                f"the coordinator and broadcast_json "
                                f"the verdict", source=src)
                        elif callee in _TRACE_ENTRY:
                            self._emit(
                                call, SEV_WARNING,
                                "host_divergent_branch",
                                f"trace entry {callee}() behind a "
                                f"branch on {src}() — hosts may compile "
                                f"divergent executables (the r13 "
                                f"pricing-divergence class); key the "
                                f"decision on broadcast state",
                                source=src)

    # ------------------------------------ rule: unverified transition

    def _enclosing_def(self, node):
        cur = self._parents.get(id(node))
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = self._parents.get(id(cur))
        return cur

    def rule_unverified_transition(self):
        calls = [n for n in ast.walk(self.tree)
                 if isinstance(n, ast.Call)
                 and _last_ident(n.func) in _TRANSITION_APPLIERS]
        if not calls:
            return
        # checker references per enclosing def (None = module level):
        # any Name/Attribute mention counts — the gate may be called,
        # passed, or imported-and-called under an alias attribute
        gated_scopes: set[int] = set()
        for node in ast.walk(self.tree):
            ident = ""
            if isinstance(node, ast.Name):
                ident = node.id
            elif isinstance(node, ast.Attribute):
                ident = node.attr
            if ident in _TRANSITION_CHECKERS:
                scope = self._enclosing_def(node)
                gated_scopes.add(id(scope) if scope is not None else 0)
        for call in calls:
            scope = self._enclosing_def(call)
            sid = id(scope) if scope is not None else 0
            if sid in gated_scopes:
                continue
            callee = _last_ident(call.func)
            self._emit(
                call, SEV_WARNING, "unverified_transition",
                f"{callee}() re-places state outside the fftrans "
                f"checker-gated path — a dropped mapping / dtype drift "
                f"/ missing gather path here surfaces as corruption "
                f"mid-restore; route through migrate_state / "
                f"verify_restore_transition (fresh-init placement at "
                f"compile is exempt: pragma it)")

    # ------------------------------------ rule: unverified rule load

    def rule_unverified_rule_load(self):
        calls = [n for n in ast.walk(self.tree)
                 if isinstance(n, ast.Call)
                 and _last_ident(n.func) in _RULE_LOADERS]
        if not calls:
            return
        gated_scopes: set[int] = set()
        for node in ast.walk(self.tree):
            ident = ""
            if isinstance(node, ast.Name):
                ident = node.id
            elif isinstance(node, ast.Attribute):
                ident = node.attr
            if ident in _RULE_CHECKERS:
                scope = self._enclosing_def(node)
                gated_scopes.add(id(scope) if scope is not None else 0)
        def _is_none(node) -> bool:
            return isinstance(node, ast.Constant) and node.value is None

        for call in calls:
            callee = _last_ident(call.func)
            if callee == "load_rule_collection":
                # the loader verifies internally when handed a config
                # (keyword or third positional) — that call IS the
                # gate. A literal None is NOT a config: the loader
                # skips verification for it.
                gated = any(kw.arg == "config" and not _is_none(kw.value)
                            for kw in call.keywords)
                if len(call.args) >= 3 and not _is_none(call.args[2]):
                    gated = True
                if gated:
                    continue
            scope = self._enclosing_def(call)
            sid = id(scope) if scope is not None else 0
            if sid in gated_scopes:
                continue
            self._emit(
                call, SEV_WARNING, "unverified_rule_load",
                f"{callee}() constructs/loads GraphXfers outside an "
                f"ffrules-verifier-consulting function — an unsound "
                f"rule injected into the search becomes a silently "
                f"wrong plan; pass config= to load_rule_collection or "
                f"route through analysis.rules.verify_rules (the "
                f"CI-swept built-in registry is exempt: pragma it)")

    # ---------------------------------- rule: raw timer in hot path

    def _timer_call(self, call) -> str:
        d = _dotted(call.func)
        parts = d.split(".")
        if len(parts) == 2 and parts[0] == "time" \
                and parts[1] in _TIME_FUNCS:
            return d
        if len(parts) == 1 and parts[0] in _BARE_TIMER_NAMES:
            return d
        return ""

    def rule_raw_timer_in_hot_path(self):
        # telemetry/ is the one place raw clock reads are the point:
        # the span/observe implementations themselves
        if "telemetry" in os.path.normpath(self.path).split(os.sep):
            return
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(h in fn.name.lower() for h in _HOT_PATH_HINTS):
                continue
            gates = self._gate_names(fn)
            timers = []
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call) or \
                        not self._timer_call(call):
                    continue
                if self._enclosing_def(call) is not fn:
                    continue  # nested defs get their own pass
                # a read inside an `if tel is not None:` branch is the
                # sanctioned gated-measurement idiom
                gated = False
                cur = self._parents.get(id(call))
                while cur is not None and cur is not fn:
                    if isinstance(cur, ast.If) and \
                            self._mentions_gate(cur.test, gates):
                        gated = True
                        break
                    cur = self._parents.get(id(cur))
                if not gated:
                    timers.append(call)
            if len(timers) < 2:
                continue  # a lone read is not a measurement pair
            second = sorted(timers, key=lambda c: (c.lineno,
                                                   c.col_offset))[1]
            self._emit(
                second, SEV_WARNING, "raw_timer_in_hot_path",
                f"{len(timers)} bare timer reads in hot-path function "
                f"{fn.name}() — a hand-rolled start/stop pair the "
                f"metrics plane never sees; wrap the region in "
                f"telemetry.span(...) or feed the delta to "
                f"telemetry.observe(...) so it lands in the mergeable "
                f"histograms", timer_reads=len(timers))

    # ------------------------------------ rule: unnamed op scope

    def rule_unnamed_op_scope(self):
        # only where op dispatch lives: the executor's forward/backward
        # paths and the ops/ package — the cost model's calibration
        # harness times ops standalone (no trace to attribute) and is
        # out of scope by construction
        parts = os.path.normpath(self.path).split(os.sep)
        if os.path.basename(self.path) != "executor.py" \
                and "ops" not in parts:
            return
        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            d = _dotted(call.func)
            if not (d.endswith(".op_def.forward")
                    or d.endswith(".op_def.backward")
                    or d in ("op_def.forward", "op_def.backward")):
                continue
            named = False
            cur = self._parents.get(id(call))
            while cur is not None:
                if isinstance(cur, ast.With):
                    for item in cur.items:
                        ce = item.context_expr
                        if isinstance(ce, ast.Call) and \
                                _last_ident(ce.func) == "named_scope":
                            named = True
                if isinstance(cur, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    break  # runtime nesting is invisible past a def
                cur = self._parents.get(id(cur))
            if named:
                continue
            self._emit(
                call, SEV_WARNING, "unnamed_op_scope",
                f"{d}() dispatched outside jax.named_scope — its device "
                f"time cannot be attributed back to the PCG node by the "
                f"ffscope profiling plane (scope/attribution.py maps "
                f"trace events via scope labels); wrap the dispatch in "
                f"`with jax.named_scope(node.name):` (a dispatch that "
                f"runs under a caller's scope is exempt: pragma it)")

    # ---------------------------------------------------------------- run

    def run(self) -> list[Finding]:
        for rule in ALL_RULES:
            if rule in self.select:
                getattr(self, f"rule_{rule}")()
        self.findings.sort(key=lambda f: f.where)
        return self.findings


def lint_source(src: str, path: str = "<string>",
                select=None) -> list[Finding]:
    """Lint one source string. Raises SyntaxError on unparseable input."""
    return _FileLint(src, path, select).run()


def lint_file(path: str, select=None) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        return lint_source(src, path, select)
    except SyntaxError as e:
        return [Finding(SEV_ERROR, "parse_error",
                        f"could not parse: {e}", pass_name=PASS_NAME,
                        where=f"{path}:{e.lineno or 0}")]


_EXCLUDE_DIRS = {"__pycache__", ".git", ".github", "node_modules"}


def iter_py_files(root: str, exclude=()):
    exclude = set(exclude)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in _EXCLUDE_DIRS and d not in exclude)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_paths(paths, select=None, exclude=()) -> list[Finding]:
    findings: list[Finding] = []
    for p in paths:
        if os.path.isdir(p):
            for f in iter_py_files(p, exclude=exclude):
                findings.extend(lint_file(f, select))
        else:
            findings.extend(lint_file(p, select))
    return findings
