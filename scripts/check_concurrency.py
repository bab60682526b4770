#!/usr/bin/env python
"""Concurrency lint for cometbft_tpu/ — the static half of the
sanitizer plane whose runtime half is cometbft_tpu/libs/lockrank.py
(docs/ANALYSIS.md documents both).  Go-side CometBFT leans on the race
detector and deadlock-ordered mutexes; this is the AST equivalent for
the Python port, in the closed-registry style scripts/check_metrics.py
proved out.

Checks (suppress a single site with a trailing `# conc: <rule>-ok`
comment — e.g. `# conc: blocking-ok` — never by widening a registry):
  C1. every `threading.Lock/RLock/Condition` construction outside
      libs/lockrank.py is a violation: locks must come from the ranked
      family (RankedLock/RankedRLock/RankedCondition) so the runtime
      rank checker sees every acquisition.  `# conc: raw-ok`
      suppresses.
  C2. every `<cv>.wait(...)` on a RankedCondition attribute must sit
      inside a `while`-predicate loop — a bare `if`/straight-line wait
      is a lost-wakeup / spurious-wakeup bug.  `wait_for` is exempt
      (it loops internally).  `# conc: wait-ok` suppresses.
  C3. no blocking call while lexically inside a `with <ranked lock>:`
      block: `.result()`, `.join()` (thread-shaped: zero positional
      args), `.get()` on queue-named receivers, `time.sleep`, and the
      device dispatch entry points in BLOCKING_ENTRY_POINTS.  Waiting
      on the SAME condition variable the `with` holds is the normal
      cv pattern and exempt.  `# conc: blocking-ok` suppresses.
  C4. every `threading.Thread(...)` / `threading.Timer(...)` must be
      daemonized (daemon=True at construction, or `<target>.daemon =
      True` before start in the same file) or registered in
      JOINED_THREADS as joined on its owner's on_stop path.
      `# conc: thread-ok` suppresses.
  C5. every `COMETBFT_TPU_*` / `SIMNET_*` environ read names a knob
      registered in KNOBS (or a dynamic family in PREFIX_KNOBS), and
      every registered knob is documented somewhere under docs/ —
      an undocumented knob is an untestable, unfindable behavior
      switch.  `# conc: knob-ok` suppresses.
  C6. every literal lock name handed to the ranked family exists in
      lockrank.LOCK_RANKS — the closed rank table is the single
      source of acquisition order.

Run directly (exits 1 on findings) or through tests/test_tools.py as a
tier-1 test.
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "cometbft_tpu"
LOCKRANK_PY = PKG / "libs" / "lockrank.py"
DOCS = REPO / "docs"

RAW_PRIMITIVES = ("Lock", "RLock", "Condition")
RANKED_FACTORIES = ("RankedLock", "RankedRLock", "RankedCondition")

# C3: method names that block by contract.  `join` is additionally
# shape-filtered (str.join takes a positional iterable; thread.join
# takes none or a timeout); `get` only on queue-shaped receivers.
BLOCKING_METHODS = {
    "result": "Future.result blocks until the window resolves",
    "join": "Thread.join blocks until the thread exits",
    "get": "queue.Queue.get blocks until an item arrives",
    "wait": "waiting on one lock while holding another inverts "
            "with any thread that blocks the other way",
}
# attribute names of device dispatch entry points that block on the
# pipeline depth semaphore or the device itself — never call these
# while holding a ranked lock
BLOCKING_ENTRY_POINTS = {
    "verify_batch": "device batch verify blocks on dispatch",
    "submit_recheck": "mempool recheck round-trips the ABCI app",
}
QUEUE_RECEIVER = re.compile(r"(queue|inbox|sched|_q)\b|_q$", re.I)

# C4: threads deliberately non-daemon AND joined on their owner's
# on_stop path ("file::attr" of the construction's assignment target)
JOINED_THREADS: set[str] = {
    # light/client.py _WindowPrefetcher: the sequential-sync prefetch
    # worker — daemonized (a wedged provider must never wedge
    # interpreter shutdown) AND joined by close() on the orderly path;
    # tests/test_light.py pins the leak regression
    "client.py::self._thread",
}

# C5: the closed env-knob registry.  One entry per knob the package
# reads; docs/ANALYSIS.md carries the authoritative table and every
# name must appear somewhere under docs/.
KNOBS = {
    # crypto/dispatch.py — verify pipeline shape
    "COMETBFT_TPU_PIPELINE_DEPTH",
    "COMETBFT_TPU_DISPATCH_DEADLINE_S",
    "COMETBFT_TPU_BROWNOUT_DEPTH",
    "COMETBFT_TPU_BROWNOUT_MAX_WINDOW",
    # crypto/devhealth.py — circuit breaker
    "COMETBFT_TPU_QUARANTINE_AFTER",
    "COMETBFT_TPU_FAULT_WINDOW_S",
    "COMETBFT_TPU_PROBE_BACKOFF_S",
    "COMETBFT_TPU_PROBE_BACKOFF_MAX_S",
    # crypto/votestream.py — streaming verifier
    "COMETBFT_TPU_VOTE_FLUSH_MS",
    "COMETBFT_TPU_VOTE_DEVICE_THRESHOLD",
    "COMETBFT_TPU_VOTE_PREWARM",
    # crypto batch/bridge thresholds
    "COMETBFT_TPU_BATCH_THRESHOLD",
    "COMETBFT_TPU_DEFERRED_THRESHOLD",
    "COMETBFT_TPU_HASH_THRESHOLD",
    "COMETBFT_TPU_SECP_THRESHOLD",
    "COMETBFT_TPU_PURE_SECP",
    "COMETBFT_TPU_PROVIDER",
    # sigcache
    "COMETBFT_TPU_SIGCACHE",
    "COMETBFT_TPU_SIGCACHE_CAPACITY",
    # device kernels / caches
    "COMETBFT_TPU_SECP_MSM",
    "COMETBFT_TPU_A_CACHE",
    "COMETBFT_TPU_A_CACHE_CAP",
    "COMETBFT_TPU_A_CACHE_MIN_K",
    "COMETBFT_TPU_A_CACHE_BYTES",
    "COMETBFT_TPU_Q_CACHE_BYTES",
    "COMETBFT_TPU_DEVICE_HASH",
    "COMETBFT_TPU_DEVICE_HASH_BLOCKS",
    # mesh / blocksync
    "COMETBFT_TPU_MESH_DEVICES",
    "COMETBFT_TPU_MESH_MIN_SPLIT",
    "COMETBFT_TPU_MESH_BENCH_N",
    "COMETBFT_TPU_BLOCKSYNC_PIPELINE",
    "COMETBFT_TPU_BLOCKSYNC_MESH_DEVICES",
    # store / state / misc
    "COMETBFT_TPU_BLOCK_CACHE",
    "COMETBFT_TPU_NATIVE_CODEC_MIN",
    "COMETBFT_TPU_KVSTORE_SNAPSHOT_INTERVAL",
    "COMETBFT_TPU_RSS_LOG",
    # lightserve/ — the coalescing light-client serving plane
    "COMETBFT_TPU_LIGHTSERVE_COALESCE",
    "COMETBFT_TPU_LIGHTSERVE_WINDOW_MS",
    "COMETBFT_TPU_LIGHTSERVE_MAX_BATCH",
    "COMETBFT_TPU_LIGHTSERVE_PLAN_DEPTH",
    "COMETBFT_TPU_LIGHTSERVE_PAYLOAD_CACHE",
    # sanitizer plane (lockrank PR)
    "COMETBFT_TPU_LOCKRANK",
    "COMETBFT_TPU_SANITIZERS",
    # crypto/sched.py — verify-plane QoS scheduler
    "COMETBFT_TPU_SCHED",
    "COMETBFT_TPU_SCHED_QUANTUM",
    "COMETBFT_TPU_SCHED_HOLD_MS",
    "COMETBFT_TPU_SCHED_BLOCKSYNC_LANE",
    "COMETBFT_TPU_SCHED_LIGHT_LANE",
    # libs/latledger.py — per-consumer verify-latency ledger
    "COMETBFT_TPU_LATLEDGER",
    "COMETBFT_TPU_LATLEDGER_CAPACITY",
    "COMETBFT_TPU_LATLEDGER_SLO_BURN",
    # libs/telspool.py — crash-safe telemetry spool (fleetobs plane)
    "COMETBFT_TPU_TELSPOOL",
    "COMETBFT_TPU_TELSPOOL_INTERVAL_S",
    "COMETBFT_TPU_TELSPOOL_SEGMENT_BYTES",
    "COMETBFT_TPU_TELSPOOL_SEGMENTS",
    # simnet
    "SIMNET_CONSENSUS_VALS",
    "SIMNET_CONSENSUS_BLOCKS",
    "SIMNET_BENCH_MESH_DEVICES",
}
# dynamically-constructed knob families (f-string names): a literal
# prefix ending in "_" read via environ must match one of these, and
# the PREFIX itself must be documented
PREFIX_KNOBS = {
    "SIMNET_CONSENSUS_",
    "SIMNET_BENCH_",
    "SIMNET_LIGHT_",
    "SIMNET_TRACE_",
    # simnet/bench.py bench_verify_contention scale overrides
    "SIMNET_CONTENTION_",
}
KNOB_RE = re.compile(r"\A(COMETBFT_TPU_|SIMNET_)[A-Z0-9_]*\Z")

SUPPRESS = {
    "C1": "# conc: raw-ok",
    "C2": "# conc: wait-ok",
    "C3": "# conc: blocking-ok",
    "C4": "# conc: thread-ok",
    "C5": "# conc: knob-ok",
}


def _iter_files(root: Path | None = None):
    root = root or PKG
    if root.is_file():
        yield root
        return
    yield from sorted(root.rglob("*.py"))


def _parents(tree: ast.AST) -> dict:
    par = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            par[child] = node
    return par


def _dotted(node: ast.AST) -> str | None:
    """`self._cv` -> "self._cv"; nested attrs/names only."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _suppressed(lines: list[str], lineno: int, rule: str) -> bool:
    mark = SUPPRESS[rule]
    ln = lines[lineno - 1] if 0 < lineno <= len(lines) else ""
    return mark in ln


def lock_ranks(path: Path | None = None) -> dict[str, int]:
    """LOCK_RANKS parsed out of libs/lockrank.py — AST only, the same
    no-import discipline as check_metrics.registered_labels."""
    tree = ast.parse((path or LOCKRANK_PY).read_text())
    for node in tree.body:
        if (isinstance(node, ast.AnnAssign)
                and isinstance(node.target, ast.Name)
                and node.target.id == "LOCK_RANKS"
                and isinstance(node.value, ast.Dict)):
            return {k.value: v.value
                    for k, v in zip(node.value.keys, node.value.values)
                    if isinstance(k, ast.Constant)
                    and isinstance(v, ast.Constant)}
        if (isinstance(node, ast.Assign)
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "LOCK_RANKS"
                and isinstance(node.value, ast.Dict)):
            return {k.value: v.value
                    for k, v in zip(node.value.keys, node.value.values)
                    if isinstance(k, ast.Constant)
                    and isinstance(v, ast.Constant)}
    return {}


def _ranked_call_name(call: ast.Call) -> str | None:
    fn = call.func
    attr = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else None)
    return attr if attr in RANKED_FACTORIES else None


def _collect_lock_attrs(tree: ast.AST) -> tuple[set[str], set[str]]:
    """(all ranked-lock value expressions, cv-only expressions) in one
    file, as dotted strings — derived from `X = *.Ranked*(...)`
    assignments so the lint is self-maintaining as locks are added."""
    locks: set[str] = set()
    cvs: set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        name = _ranked_call_name(node.value)
        if name is None:
            continue
        for tgt in node.targets:
            d = _dotted(tgt)
            if d is None:
                continue
            locks.add(d)
            if name == "RankedCondition":
                cvs.add(d)
    return locks, cvs


def _in_while(node: ast.AST, parents: dict) -> bool:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.While):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return False
        cur = parents.get(cur)
    return False


def _walk_scope(body: list[ast.stmt]):
    """Walk statements without descending into nested function bodies
    (a def inside a with-block does not run under the lock)."""
    stack = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def run_checks(root: Path | None = None,
               lockrank_path: Path | None = None,
               docs_root: Path | None = None) -> list[str]:
    """All findings as human-readable strings; empty means clean."""
    findings: list[str] = []
    ranks = lock_ranks(lockrank_path)
    if not ranks:
        return ["LOCK_RANKS not found in libs/lockrank.py "
                "(parser broken?)"]
    lockrank_file = (lockrank_path or LOCKRANK_PY).resolve()
    docs_text = "".join(p.read_text()
                        for p in sorted((docs_root or DOCS).glob("*.md")))
    knobs_seen: set[str] = set()

    for py in _iter_files(root):
        text = py.read_text()
        lines = text.split("\n")
        tree = ast.parse(text)
        try:
            rel = str(py.relative_to(REPO))
        except ValueError:
            rel = py.name
        parents = _parents(tree)
        lock_exprs, cv_exprs = _collect_lock_attrs(tree)
        is_lockrank = py.resolve() == lockrank_file

        for node in ast.walk(tree):
            # ---- C1: raw primitive constructions --------------------
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in RAW_PRIMITIVES
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "threading"
                    and not is_lockrank
                    and not _suppressed(lines, node.lineno, "C1")):
                findings.append(
                    f"{rel}:{node.lineno}: [C1] raw threading."
                    f"{node.func.attr}() — construct lockrank."
                    f"Ranked{node.func.attr} so the rank checker sees "
                    "every acquisition")
            if (isinstance(node, ast.ImportFrom)
                    and node.module == "threading"
                    and not is_lockrank
                    and any(a.name in RAW_PRIMITIVES
                            for a in node.names)
                    and not _suppressed(lines, node.lineno, "C1")):
                findings.append(
                    f"{rel}:{node.lineno}: [C1] `from threading import "
                    "Lock/RLock/Condition` bypasses the ranked family")

            # ---- C2: cv.wait must sit in a while loop ---------------
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "wait"):
                recv = _dotted(node.func.value)
                if (recv in cv_exprs
                        and not _in_while(node, parents)
                        and not _suppressed(lines, node.lineno, "C2")):
                    findings.append(
                        f"{rel}:{node.lineno}: [C2] bare {recv}.wait() "
                        "outside a while-predicate loop — spurious "
                        "wakeups and missed notifies require "
                        "`while not pred: cv.wait()`")

            # ---- C4: thread lifecycle -------------------------------
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("Thread", "Timer")
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "threading"):
                daemon = any(
                    kw.arg == "daemon"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is True
                    for kw in node.keywords)
                tgt = None
                par = parents.get(node)
                if isinstance(par, ast.Assign):
                    tgt = _dotted(par.targets[0])
                if not daemon and tgt is not None:
                    # `<tgt>.daemon = True` anywhere in the file
                    # (the Timer pattern in consensus/ticker.py)
                    short = tgt.split(".")[-1]
                    pat = re.compile(
                        r"\.%s\.daemon\s*=\s*True|"
                        r"\b%s\.daemon\s*=\s*True"
                        % (re.escape(short), re.escape(short)))
                    daemon = bool(pat.search(text))
                key = f"{py.name}::{tgt or '<anonymous>'}"
                if (not daemon and key not in JOINED_THREADS
                        and not _suppressed(lines, node.lineno, "C4")):
                    findings.append(
                        f"{rel}:{node.lineno}: [C4] thread {key} is "
                        "neither daemonized nor registered in "
                        "JOINED_THREADS as joined on on_stop — a "
                        "non-daemon leak hangs interpreter shutdown")

            # ---- C5: env-knob registry ------------------------------
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                v = node.value
                if KNOB_RE.match(v) and not v.endswith("_"):
                    par = parents.get(node)
                    gp = parents.get(par)
                    involved = False
                    for anc in (par, gp):
                        if isinstance(anc, ast.Call):
                            f = anc.func
                            d = _dotted(f) or ""
                            if d.endswith("environ.get") or \
                                    d.endswith("getenv"):
                                involved = True
                        if isinstance(anc, ast.Subscript):
                            d = _dotted(anc.value) or ""
                            if d.endswith("environ"):
                                involved = True
                    if involved:
                        if v not in KNOBS and not any(
                                v.startswith(p) for p in PREFIX_KNOBS):
                            if not _suppressed(lines, node.lineno,
                                               "C5"):
                                findings.append(
                                    f"{rel}:{node.lineno}: [C5] env "
                                    f"knob {v!r} is not registered in "
                                    "check_concurrency.KNOBS")
                        else:
                            knobs_seen.add(v)
                elif KNOB_RE.match(v) and v.endswith("_"):
                    # f-string family prefix
                    par = parents.get(node)
                    if isinstance(par, ast.JoinedStr):
                        if v not in PREFIX_KNOBS and not _suppressed(
                                lines, node.lineno, "C5"):
                            findings.append(
                                f"{rel}:{node.lineno}: [C5] dynamic "
                                f"env-knob family {v!r} is not "
                                "registered in PREFIX_KNOBS")

            # ---- C6: ranked names exist in the table ----------------
            if isinstance(node, ast.Call) and \
                    _ranked_call_name(node) is not None:
                name_arg = None
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    name_arg = node.args[0].value
                for kw in node.keywords:
                    if kw.arg == "name" and \
                            isinstance(kw.value, ast.Constant) and \
                            isinstance(kw.value.value, str):
                        name_arg = kw.value.value
                if name_arg is not None and name_arg not in ranks:
                    findings.append(
                        f"{rel}:{node.lineno}: [C6] lock name "
                        f"{name_arg!r} is not in lockrank.LOCK_RANKS")

            # ---- C3: blocking call under a ranked lock --------------
            if isinstance(node, ast.With):
                held = [(_dotted(item.context_expr), item.context_expr)
                        for item in node.items]
                held_locks = [d for d, _ in held if d in lock_exprs]
                if not held_locks:
                    continue
                for sub in _walk_scope(node.body):
                    if not (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)):
                        continue
                    m = sub.func.attr
                    recv = _dotted(sub.func.value)
                    hit = None
                    if m in ("wait", "wait_for"):
                        # waiting on the held cv itself is the pattern
                        if recv not in held_locks and recv in cv_exprs:
                            hit = BLOCKING_METHODS["wait"]
                        elif recv == "time":
                            pass
                    elif m == "result":
                        hit = BLOCKING_METHODS["result"]
                    elif m == "join":
                        # str.join takes a positional iterable;
                        # thread.join takes none or a timeout
                        if not sub.args or (
                                len(sub.args) == 1
                                and isinstance(sub.args[0],
                                               ast.Constant)
                                and isinstance(sub.args[0].value,
                                               (int, float))):
                            hit = BLOCKING_METHODS["join"]
                    elif m == "get":
                        if recv and QUEUE_RECEIVER.search(recv):
                            hit = BLOCKING_METHODS["get"]
                    elif m == "sleep" and recv == "time":
                        hit = "time.sleep stalls every thread queued "\
                              "on the held lock"
                    elif m in BLOCKING_ENTRY_POINTS:
                        hit = BLOCKING_ENTRY_POINTS[m]
                    if hit and not _suppressed(lines, sub.lineno,
                                               "C3"):
                        findings.append(
                            f"{rel}:{sub.lineno}: [C3] blocking call "
                            f"{(recv + '.') if recv else ''}{m}() "
                            f"while holding {held_locks} — {hit}")

    # ---- C5 (docs half): every registered knob is documented --------
    for knob in sorted(KNOBS):
        if knob not in docs_text:
            findings.append(
                f"scripts/check_concurrency.py: [C5] registered knob "
                f"{knob} is not documented anywhere under docs/")
    for prefix in sorted(PREFIX_KNOBS):
        if prefix not in docs_text:
            findings.append(
                f"scripts/check_concurrency.py: [C5] knob family "
                f"{prefix}* is not documented anywhere under docs/")
    return findings


def main() -> int:
    findings = run_checks()
    for f in findings:
        print(f"check_concurrency: {f}", file=sys.stderr)
    if findings:
        print(f"check_concurrency: {len(findings)} finding(s)",
              file=sys.stderr)
        return 1
    n = len(lock_ranks())
    print(f"check_concurrency: OK ({n} ranked locks)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
