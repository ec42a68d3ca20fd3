"""Rule catalog: serving invariants this repo depends on, as AST checks.

Every rule is grounded in a bug class the engine has already hit or is one
refactor away from hitting (see docs/api.md "Static analysis & sanitizer"
for the rationale catalog):

- RPR001 donation-after-use — a buffer handed into a donating jitted call
  (``donate_argnums``, or the ``decode_state``/``absorb_decode_state``
  donation-aware pairs) is read again before rebinding. On TPU the donated
  buffer is dead after the call; off-TPU the read silently works, so only
  static analysis (and the PoolSanitizer's poisoning) catches it.
- RPR002 refcount-balance — a function takes pool references
  (``alloc``/``ref``/``acquire``/``begin``/``extend``) and then performs
  fallible work with no ``unref``/``drop``/``release``/``abandon`` on any
  exception path: one raise and the pages leak as permanently-active.
  Relay-KV note: relay publication (``_finish``/``_relay_publish``) is a
  RELEASE-side discipline the AST rule cannot see — every page the tree
  adopts must be ``unref``'d to CACHED (never left ACTIVE, never ``drop``'d
  out from under the tree) in the same ``_finish``, and non-adopted private
  pages must still be hard-dropped. The runtime half enforces it: the
  PoolSanitizer's step census treats relay-published pages as first-class
  (an ACTIVE holderless relay page is diagnosed by name) and
  ``check_index`` rejects a tree that serves a FREE page.
- RPR003 host-sync-in-hot-path — ``block_until_ready``/``np.asarray``/
  ``.item()``/``float(x[i])`` inside scheduler/decode step loops serializes
  the device pipeline per step (or worse, per token).
- RPR004 unbucketed-shape-into-jit — a dynamic length-derived value reaches
  a jitted call's array shapes without the pow2 bucketing helper, so jit
  retraces grow with prompt/table length instead of O(log).
- RPR005 side-effect-in-jit — Python side effects (``self.x += 1``,
  ``print``, ``time.*``) inside a jit-traced function run once per TRACE,
  not per call: counters silently stop counting after the first step.
- RPR006 metrics-instrument-in-step — registry ``counter``/``gauge``/
  ``histogram`` get-or-create inside per-step code; instruments must be
  hoisted to ``__init__``/``_init_metrics`` so hot paths hold direct refs.
- RPR007 host-materialized-pool-pages — ``np.asarray``/``jax.device_get``
  on the paged pool's page buffers anywhere outside ``kvcache/swap.py``.
  The swap tier is the ONE sanctioned device->host path for pool KV (it is
  timed, fed to the preemption cost model, and censused by the sanitizer);
  an ad-hoc host copy elsewhere serializes the device pipeline against the
  whole pool and produces KV the swap census cannot account for.
"""
from __future__ import annotations

import ast
import re

from repro.analysis.core import (Finding, ModuleContext, Rule, attr_chain,
                                 call_name, receiver_name, walk_calls)

# pool-ish receivers: method calls on these names are refcount operations
_POOLISH = re.compile(r"^(pool|mgr|manager|block_pool|blockpool)$")
ACQUIRE_METHODS = {"alloc", "ref", "acquire", "begin", "extend", "retain"}
# swap_out / discard_swapped are the swap tier's release-side transitions
# (device rows relinquished to the pool's SWAPPED/FREE populations): a
# rollback handler that re-parks reclaimed pages IS release discipline
RELEASE_METHODS = {"unref", "drop", "release", "abandon", "swap_out",
                   "discard_swapped"}

# calls that cannot plausibly raise between an acquire and its release
_SAFE_CALLS = {"append", "extend", "touch", "record_hit", "move_to_end",
               "setdefault", "get", "pop", "popitem", "items", "keys",
               "values", "add", "remove", "discard", "int", "len", "str",
               "float", "bool", "max", "min", "list", "tuple", "dict", "set",
               "sorted", "range", "hash", "isinstance", "copy", "enumerate",
               "zip"}

# names of the pow2 bucketing helpers that make a dynamic shape jit-safe
BUCKET_HELPERS = {"next_pow2", "pow2_bucket", "bucket_pow2"}

# jitted-call entry points by convention: the engine's jitted steps are
# stored/called as ``step``/``_step`` (DecodeWorker._step, StackedDecoders
# ._step, decoders[mid].step) — plus anything assigned from jax.jit(...)
_JIT_ENTRY_NAMES = {"step", "_step"}

# functions that ARE the per-step hot path (RPR003/RPR006 scope): decode and
# chunk-packing loops of the scheduler/engine/decode plane
HOT_FUNCS = {"step", "decode_step", "_decode_phase", "_batched_step",
             "_run_chunks", "_grow_tail_pages", "_promote", "_plan_chunks",
             "_reap_finished"}
_HOT_CLASS = re.compile(r"(Scheduler|Engine|Plane|Decoder|Worker)")


def _functions(ctx: ModuleContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _is_jax_jit(call: ast.Call) -> bool:
    """``jax.jit(...)``, or ``repro.runtime.donating_jit(fun, argnums)``."""
    chain = attr_chain(call.func)
    return chain[-2:] == ["jax", "jit"] or chain in (["jit"],
                                                     ["donating_jit"])


def _donated_positions(call: ast.Call, ctx: ModuleContext):
    """Parse ``donate_argnums`` from a jit call: a constant tuple, an
    IfExp over tuples (the repo's ``(0,) if tpu else ()`` idiom), or a Name
    bound to either nearby. Returns a set of positions, or None (no
    donation), or 'all' when unparseable (conservative)."""
    kw = next((k.value for k in call.keywords if k.arg == "donate_argnums"),
              None)
    if kw is None and call_name(call) == "donating_jit" and len(call.args) > 1:
        kw = call.args[1]
    if kw is None:
        return None

    def positions(node):
        if isinstance(node, ast.Tuple):
            out = set()
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.add(e.value)
            return out
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return {node.value}
        if isinstance(node, ast.IfExp):
            return positions(node.body) | positions(node.orelse)
        if isinstance(node, ast.Name):
            # resolve a simple local/module binding of the name
            fn = ctx.enclosing_function(call)
            scope = fn if fn is not None else ctx.tree
            for sub in ast.walk(scope):
                if (isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Name)
                        and sub.targets[0].id == node.id):
                    return positions(sub.value)
            return None
        return None

    got = positions(kw)
    return got if got is not None else "all"


def _jit_assignments(ctx: ModuleContext):
    """{last-name-of-target: donated-positions} for every
    ``X = jax.jit(...)`` in the module (donated-positions may be an empty
    set — still a jit entry for RPR004)."""
    out: dict = {}
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        if not (isinstance(node.value, ast.Call)
                and _is_jax_jit(node.value)):
            continue
        tgt = node.targets[0]
        chain = attr_chain(tgt)
        if not chain:
            continue
        donated = _donated_positions(node.value, ctx)
        out[chain[-1]] = donated if donated is not None else set()
    return out


def _ordered_nodes(fn, kind):
    out = [n for n in ast.walk(fn) if isinstance(n, kind)]
    out.sort(key=lambda n: (getattr(n, "lineno", 0),
                            getattr(n, "col_offset", 0)))
    return out


# ======================================================================
class DonationAfterUse(Rule):
    rule_id = "RPR001"
    title = "donation-after-use"

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings = []
        donators = {name: pos for name, pos in _jit_assignments(ctx).items()
                    if pos == "all" or pos}
        for fn in _functions(ctx):
            findings.extend(self._check_fn(ctx, fn, donators))
        return findings

    def _check_fn(self, ctx, fn, donators):
        # vars holding donation-aware pool state (decode_state /
        # pool_state hand out buffers that a donating step consumes)
        handles: set[str] = set()
        donated: dict[str, ast.Call] = {}     # var -> donating call
        exempt: set[int] = set()              # Name node ids at donation site
        findings = []

        def key(n):
            # Assigns sort at their END so ``state = _step(state)`` processes
            # the donating call first, THEN the rebind clears it — reads
            # after a rebinding line must not flag
            if isinstance(n, ast.Assign):
                return (getattr(n, "end_lineno", n.lineno),
                        getattr(n, "end_col_offset", n.col_offset), 1)
            return (getattr(n, "lineno", 0), getattr(n, "col_offset", 0), 0)

        events = sorted(
            (n for n in ast.walk(fn)
             if isinstance(n, (ast.Call, ast.Name, ast.Assign))),
            key=key)
        for node in events:
            if isinstance(node, ast.Assign):
                # rebinding clears donation/handle state for the target
                for tgt in node.targets:
                    for t in ast.walk(tgt):
                        if isinstance(t, ast.Name):
                            donated.pop(t.id, None)
                            handles.discard(t.id)
                if (isinstance(node.value, ast.Call)
                        and call_name(node.value) in ("decode_state",
                                                      "pool_state")
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    handles.add(node.targets[0].id)
                continue
            if isinstance(node, ast.Call):
                name = call_name(node)
                pos = donators.get(name)
                if pos is not None:
                    for i, a in enumerate(node.args):
                        if pos != "all" and i not in pos:
                            continue
                        if isinstance(a, ast.Name):
                            donated[a.id] = node
                            exempt.add(id(a))
                elif name in _JIT_ENTRY_NAMES:
                    # handing a pool-state handle into a jitted step donates
                    # it on TPU (the decode_state/absorb pair contract)
                    for a in node.args:
                        if isinstance(a, ast.Name) and a.id in handles:
                            donated[a.id] = node
                            exempt.add(id(a))
                continue
            # Name loads: a read of a donated var after the donating call
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in donated
                    and id(node) not in exempt):
                site = donated[node.id]
                if (node.lineno, node.col_offset) > (site.lineno,
                                                     site.col_offset):
                    findings.append(self.finding(
                        ctx, node,
                        f"'{node.id}' was donated into "
                        f"'{call_name(site)}(...)' on line {site.lineno} and "
                        f"is read again before rebinding — after a donated "
                        f"jitted step the buffer is dead on TPU "
                        f"(decode_state/absorb_decode_state contract)"))
                    del donated[node.id]       # one finding per donation
        return findings


# ======================================================================
class RefcountBalance(Rule):
    rule_id = "RPR002"
    title = "refcount-balance"
    applies_to_tests = False        # tests corrupt pools on purpose

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings = []
        for fn in _functions(ctx):
            acquires = []
            has_release_handler = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Try):
                    guarded = list(node.finalbody)
                    for h in node.handlers:
                        guarded.extend(h.body)
                    for g in guarded:
                        for c in walk_calls(g):
                            if (call_name(c) in RELEASE_METHODS
                                    and (_POOLISH.match(receiver_name(c))
                                         or receiver_name(c) == "self")):
                                has_release_handler = True
            for c in walk_calls(fn):
                if (call_name(c) in ACQUIRE_METHODS
                        and _POOLISH.match(receiver_name(c))):
                    acquires.append(c)
            if not acquires or has_release_handler:
                continue
            first = min(acquires,
                        key=lambda c: (c.lineno, c.col_offset))
            risky = [
                c for c in walk_calls(fn)
                if (c.lineno, c.col_offset) > (first.lineno, first.col_offset)
                and call_name(c) not in _SAFE_CALLS
                and not (call_name(c) in ACQUIRE_METHODS
                         and _POOLISH.match(receiver_name(c)))
                and not (call_name(c) in RELEASE_METHODS
                         and _POOLISH.match(receiver_name(c)))]
            if risky:
                findings.append(self.finding(
                    ctx, first,
                    f"'{receiver_name(first)}.{call_name(first)}(...)' takes "
                    f"pool references but the enclosing function performs "
                    f"fallible work afterwards (e.g. "
                    f"'{call_name(risky[0])}(...)' on line "
                    f"{risky[0].lineno}) with no unref/drop/release/abandon "
                    f"on any exception path — a raise leaks the pages as "
                    f"permanently active"))
        return findings


# ======================================================================
class HostSyncInHotPath(Rule):
    rule_id = "RPR003"
    title = "host-sync-in-hot-path"
    applies_to_tests = False

    def _is_hot(self, ctx, fn) -> bool:
        if fn.name not in HOT_FUNCS:
            return False
        cls = ctx.enclosing_class(fn)
        if cls is not None and _HOT_CLASS.search(cls.name):
            return True
        return "serving/" in ctx.path

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings = []
        for fn in _functions(ctx):
            if not self._is_hot(ctx, fn):
                continue
            for c in walk_calls(fn):
                name = call_name(c)
                recv = receiver_name(c)
                if name == "block_until_ready":
                    findings.append(self.finding(
                        ctx, c, "jax.block_until_ready in a per-step hot "
                        "path serializes the device pipeline every step"))
                elif name == "item" and not c.args and not c.keywords:
                    findings.append(self.finding(
                        ctx, c, ".item() on a device value in a per-step "
                        "hot path forces a device->host sync"))
                elif name == "asarray" and recv in ("np", "numpy", "onp"):
                    findings.append(self.finding(
                        ctx, c, "np.asarray in a per-step hot path copies "
                        "device memory to host synchronously"))
                elif (name in ("float", "int") and len(c.args) == 1
                        and isinstance(c.args[0], ast.Subscript)):
                    findings.append(self.finding(
                        ctx, c, f"{name}() on an indexed (device) value in "
                        f"a per-step hot path forces one device->host sync "
                        f"per element"))
        return findings


# ======================================================================
class UnbucketedShapeIntoJit(Rule):
    rule_id = "RPR004"
    title = "unbucketed-shape-into-jit"

    @staticmethod
    def _dynamic_len(expr) -> bool:
        """Expression derives a length from runtime data: contains a
        ``len(x)`` where x is not rooted at self (self attrs are stable
        across steps), or a ``.shape`` access."""
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call) and call_name(sub) == "len" \
                    and sub.args:
                chain = attr_chain(sub.args[0])
                if chain and chain[0] == "self":
                    continue
                return True
            if isinstance(sub, ast.Attribute) and sub.attr == "shape":
                return True
        return False

    @staticmethod
    def _bucketed(expr) -> bool:
        return any(isinstance(sub, ast.Call)
                   and call_name(sub) in BUCKET_HELPERS
                   for sub in ast.walk(expr))

    def check(self, ctx: ModuleContext) -> list[Finding]:
        jit_names = set(_jit_assignments(ctx)) | _JIT_ENTRY_NAMES
        findings = []
        for fn in _functions(ctx):
            entry_calls = [c for c in walk_calls(fn)
                           if call_name(c) in jit_names]
            if not entry_calls:
                continue
            shape_vars: dict[str, ast.Assign] = {}
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and self._dynamic_len(node.value)
                        and not self._bucketed(node.value)):
                    shape_vars[node.targets[0].id] = node
            if not shape_vars:
                continue
            flagged: set[str] = set()
            for c in walk_calls(fn):
                is_ctor = call_name(c) in ("zeros", "full", "empty", "ones")
                is_entry = call_name(c) in jit_names
                if not (is_ctor or is_entry):
                    continue
                for a in list(c.args) + [k.value for k in c.keywords]:
                    for sub in ast.walk(a):
                        if (isinstance(sub, ast.Name)
                                and isinstance(sub.ctx, ast.Load)
                                and sub.id in shape_vars
                                and sub.id not in flagged):
                            flagged.add(sub.id)
                            site = shape_vars[sub.id]
                            findings.append(self.finding(
                                ctx, site,
                                f"'{sub.id}' is a runtime length that "
                                f"reaches a jitted call's array shapes "
                                f"without pow2 bucketing (next_pow2) — jit "
                                f"retraces will grow with the data instead "
                                f"of O(log)"))
        return findings


# ======================================================================
class SideEffectInJit(Rule):
    rule_id = "RPR005"
    title = "side-effect-in-jit"

    _IMPURE_ROOTS = {"time", "random"}

    def _jit_target_defs(self, ctx: ModuleContext):
        """FunctionDefs that are jit-traced: passed by name to jax.jit, or
        decorated with jax.jit / partial(jax.jit, ...)."""
        jitted_names: set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and _is_jax_jit(node) and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Name):
                    jitted_names.add(a0.id)
        targets = []
        for fn in _functions(ctx):
            if fn.name in jitted_names:
                targets.append(fn)
                continue
            for dec in fn.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                chain = attr_chain(d)
                if chain[-2:] == ["jax", "jit"] or chain == ["jit"]:
                    targets.append(fn)
                    break
                if isinstance(dec, ast.Call) and chain[-1:] == ["partial"]:
                    if any(attr_chain(a)[-2:] == ["jax", "jit"]
                           for a in dec.args):
                        targets.append(fn)
                        break
        # nested defs inside a traced function are traced too
        out = []
        seen = set()
        for fn in targets:
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and id(sub) not in seen:
                    seen.add(id(sub))
                    out.append(sub)
        return out

    @staticmethod
    def _walk_own(fn):
        """Walk fn's body, pruning nested defs — each nested def is its own
        entry in the target list, so its body is visited exactly once."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(node))

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings = []
        for fn in self._jit_target_defs(ctx):
            for node in self._walk_own(fn):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    tgts = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in tgts:
                        chain = attr_chain(t)
                        if len(chain) >= 2 and chain[0] == "self":
                            findings.append(self.finding(
                                ctx, node,
                                f"assignment to '{'.'.join(chain)}' inside "
                                f"a jit-traced function runs once per "
                                f"TRACE, not per call — hoist the side "
                                f"effect out of the traced body"))
                elif isinstance(node, (ast.Global, ast.Nonlocal)):
                    findings.append(self.finding(
                        ctx, node, "global/nonlocal mutation inside a "
                        "jit-traced function runs once per trace"))
                elif isinstance(node, ast.Call):
                    name = call_name(node)
                    chain = attr_chain(node.func)
                    if name == "print":
                        findings.append(self.finding(
                            ctx, node, "print inside a jit-traced function "
                            "fires once per trace (use jax.debug.print)"))
                    elif chain and chain[0] in self._IMPURE_ROOTS:
                        findings.append(self.finding(
                            ctx, node,
                            f"'{'.'.join(chain)}(...)' inside a jit-traced "
                            f"function is evaluated at trace time only"))
        return findings


# ======================================================================
class MetricsInstrumentInStep(Rule):
    rule_id = "RPR006"
    title = "metrics-instrument-in-step"
    applies_to_tests = False

    _ALLOWED_FUNCS = {"__init__", "_init_metrics", "__post_init__"}
    _RECEIVER = re.compile(r"(^reg$|registry$)")

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node) in ("counter", "gauge", "histogram")
                    and self._RECEIVER.search(receiver_name(node))):
                continue
            fn = ctx.enclosing_function(node)
            if fn is None or fn.name in self._ALLOWED_FUNCS:
                continue
            findings.append(self.finding(
                ctx, node,
                f"registry.{call_name(node)}(...) get-or-create inside "
                f"'{fn.name}' — instruments must be hoisted to __init__/"
                f"_init_metrics so per-step code holds direct references"))
        return findings


# ======================================================================
class HostMaterializedPoolPages(Rule):
    rule_id = "RPR007"
    title = "host-materialized-pool-pages"
    applies_to_tests = False        # tests assert on host copies on purpose

    #: the one sanctioned device->host path for pool page KV
    _SANCTIONED = "kvcache/swap.py"
    #: names that identify an expression as pool page state: the pool's
    #: buffer attributes, the pool object itself, and the whole-pool
    #: pytree accessor the swap tier gathers from
    _POOL_TOKENS = {"k_groups", "v_groups", "k_tail", "v_tail",
                    "kvpool", "kv_pool", "pool_state"}

    def check(self, ctx: ModuleContext) -> list[Finding]:
        if ctx.path.replace("\\", "/").endswith(self._SANCTIONED):
            return []
        findings = []
        for c in walk_calls(ctx.tree):
            name = call_name(c)
            recv = receiver_name(c)
            if not ((name == "asarray" and recv in ("np", "numpy", "onp"))
                    or name == "device_get"):
                continue
            toks: set[str] = set()
            for a in list(c.args) + [k.value for k in c.keywords]:
                for sub in ast.walk(a):
                    if isinstance(sub, ast.Name):
                        toks.add(sub.id)
                    elif isinstance(sub, ast.Attribute):
                        toks.add(sub.attr)
            if toks & self._POOL_TOKENS:
                findings.append(self.finding(
                    ctx, c,
                    f"'{name}(...)' materializes pool page buffers on the "
                    f"host outside {self._SANCTIONED} — the swap tier is "
                    f"the one sanctioned device->host path for pool KV "
                    f"(timed for the preemption cost model, censused by the "
                    f"sanitizer); an ad-hoc host copy serializes the device "
                    f"pipeline and escapes the swap census"))
        return findings


ALL_RULES = [DonationAfterUse(), RefcountBalance(), HostSyncInHotPath(),
             UnbucketedShapeIntoJit(), SideEffectInJit(),
             MetricsInstrumentInStep(), HostMaterializedPoolPages()]

RULES_BY_ID = {r.rule_id: r for r in ALL_RULES}
