"""dlt-lint: the AST lint enforcing project rules the runtime can't.

Rules (ids in parentheses; suppress a line with ``# dlt: allow(<rule>)``,
comma-separate for several — the pragma documents WHY at the site):

* **bare-except** — ``except:`` catches SystemExit/KeyboardInterrupt and
  hides the watchdog's StallError; always name the exception;
* **swallowed-exception** — ``except Exception:`` (or BaseException) whose
  body is only ``pass``: a failure mode the operator can never see. Either
  narrow the type, handle it, or pragma it with the reason it is safe;
* **lock-with** — lock/condition ``.acquire()`` called explicitly: lock
  discipline in this codebase is ``with`` only (a raised exception between
  acquire and release leaks the lock and wedges the Batcher/gateway
  forever). Applies to receivers whose name looks lock-ish
  (lock/cond/mutex/sem);
* **thread-daemon** — ``threading.Thread(...)`` without an explicit
  ``daemon=``: a forgotten non-daemon thread turns every crash into a
  hang at interpreter exit (the watchdog/prober/writer threads must never
  outlive the process). Thread *subclasses* must pass ``daemon=`` in their
  ``super().__init__`` call;
* **float64** — ``float64`` dtype literals in device-side packages
  (ops/models/parallel/runtime): one f64 constant silently promotes a
  whole matmul chain (the graph auditor catches the traced result; this
  catches the source). Host-side precomputation (rope tables) carries a
  pragma;
* **host-sync** — ``np.asarray`` / ``np.array`` / ``jax.device_get`` /
  ``<device>.memory_stats()`` in the hot packages (runtime/parallel): each
  is a potential blocking device→host sync (or a runtime round trip) in
  the serving loop. The sanctioned fetch sites carry pragmas —
  which doubles as the canonical list of blessed host syncs the
  host_sync_guard sanitizer allows (``memory_stats`` is blessed only at
  the cold-path HBM-ledger site, runtime/profiling.py);
* **trace-hot-emit** — ``trace.event(...)`` / ``TRACER.event(...)`` inside
  a ``for``/``while`` loop body in the emitting packages
  (runtime/parallel/server), or an emit call constructing a dict literal
  anywhere in them: per-iteration span emission must go through a
  pre-bound ``Trace.bind(...)`` / ``Tracer.bind_global(...)`` emitter
  (one tuple append per event — no name/keys re-tupling, no dict
  allocation in the decode/spec_step/Batcher inner loops;
  runtime/tracing.py Emitter). The server scope exists because the
  Batcher's step loop and the gateway's retry loop are exactly where the
  goodput-ledger and batch-timeline emits live; their sanctioned
  once-per-request/once-per-decision cold sites carry pragmas;
* **sentinel-release** — a class that subscribes a ``RecompileSentinel``
  (``self.x = RecompileSentinel(...).start()``) without a
  ``close``/``stop``/``__exit__`` method that calls ``self.x.stop()``:
  compile-event subscriptions are PROCESS-global (the jax registry has no
  unregister), so a teardown path that forgets the release leaks a
  sealed sentinel past its engine's lifetime — and a leaked SEALED FATAL
  sentinel kills every later engine build in the process (the
  cross-suite-pollution class the supervisor's rebuild path releases
  explicitly; runtime/engine.py ``close()`` is the reference shape);
* **env-surface** — an ``os.environ`` / ``getenv`` read of a ``DLT_*``
  variable whose name is missing from ``server/api.py``'s
  ``DLT_ENV_SURFACE`` registry (the ``/debug/config`` payload's declared
  knob surface) or from README/docs: every env knob the package reads
  must be debuggable from a running replica and documented, or it is
  config-surface drift — a flag operators cannot discover. The rule only
  fires when lint runs with repo-root context (``lint_paths``/CLI; plain
  ``lint_source`` has no cross-file registry to check against);
* **thread-release** — the sentinel-release rule's thread edition: a
  class holding a gateway-owned background loop (``FleetScraper``,
  ``Autoscaler``, ``HealthProber``, ``GatewayPeering`` — directly or via
  a local alias, ``x = FleetScraper(...); self.s = x``) without a
  ``close``/``stop``/``shutdown``/``server_close``/``__exit__`` method
  calling ``self.s.stop()``: these loops actuate against the fleet
  (scrape, drain, gossip), so one leaked past its server's teardown
  keeps scraping/draining from a gateway that no longer exists — and an
  in-process gateway restart (the crash-only tests instantiate the
  server twice) doubles every control loop.

The CLI lives at ``scripts/dlt_lint.py``; CI runs it over the tree.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path

ALL_RULES = (
    "bare-except",
    "swallowed-exception",
    "lock-with",
    "thread-daemon",
    "float64",
    "host-sync",
    "trace-hot-emit",
    "sentinel-release",
    "thread-release",
    "env-surface",
)

_PRAGMA_RE = re.compile(r"#\s*dlt:\s*allow\(([^)]*)\)")
_LOCKISH_RE = re.compile(r"(lock|cond|mutex|sem)", re.IGNORECASE)
_TRACEISH_RE = re.compile(r"^(tr|trace|tracer|TRACER)$")

#: packages where a float64 literal is device-side poison
FLOAT64_SCOPE = ("ops", "models", "parallel", "runtime", "formats")
#: packages whose np.asarray/np.array sites are potential host syncs
HOST_SYNC_SCOPE = ("runtime", "parallel")
#: packages whose loops must emit spans through pre-bound emitters: the
#: hot packages PLUS the server (Batcher step loop, gateway retry loop,
#: router decision path (server/router.py), disagg transfer path, and the
#: fleet control plane — scheduler admission/preemption loops
#: (server/scheduler.py), autoscaler ticks (server/autoscaler.py), the
#: load twin's stub decode loop (server/loadtwin.py) — the goodput-ledger
#: /batch-timeline/gw_route/kv_transfer/scheduler-decision emission
#: sites). The KV movement layer (runtime/kv_transport.py) rides the
#: `runtime` prefix: its transport fetch loops, the per-segment
#: insert/extract loops, AND the receipt-verification checksum loop
#: (verify_transfer's per-doubling-segment pass — emit-free by design:
#: the one `kv_integrity` event per fetch lands in DisaggClient.fetch
#: AFTER the peer loop) are in scope like every other hot path.
TRACE_EMIT_SCOPE = ("runtime", "parallel", "server")
#: packages whose classes must pair a sentinel subscription with a
#: teardown release (engine lifecycles live here)
SENTINEL_SCOPE = ("runtime", "server", "analysis")
#: class names whose instances are gateway-owned background loops: held
#: as a ``self.<attr>`` they must be released by a teardown method
#: (thread-release); all four expose ``.stop()``
THREAD_OWNER_CLASSES = (
    "FleetScraper", "Autoscaler", "HealthProber", "GatewayPeering",
)
#: method names that count as a teardown site for thread-release —
#: sentinel-release's set plus the http.server lifecycle pair the
#: gateway/api servers implement
RELEASE_METHODS = (
    "close", "stop", "shutdown", "server_close", "__exit__", "__del__",
)
#: packages whose DLT_* env reads must be declared + documented
#: (env-surface); scripts/ are operator-side and read what they document
#: themselves
ENV_SURFACE_SCOPE = ("distributed_llama_tpu",)
#: DLT_* names in markdown docs count as documented wherever they appear
_DOC_ENV_RE = re.compile(r"\bDLT_[A-Z0-9_]+\b")


def declared_env_surface(root) -> set | None:
    """The ``DLT_ENV_SURFACE`` registry tuple from server/api.py (the
    /debug/config declared knob surface), parsed statically; None when the
    file or registry is absent (rule degrades to docs-only)."""
    api = Path(root) / "distributed_llama_tpu" / "server" / "api.py"
    if not api.exists():
        return None
    try:
        tree = ast.parse(api.read_text())
    except SyntaxError:
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "DLT_ENV_SURFACE"
            for t in node.targets
        ):
            try:
                return set(ast.literal_eval(node.value))
            except (ValueError, SyntaxError):
                return None
    return None


def documented_env_vars(root) -> set | None:
    """Every DLT_* name mentioned anywhere in README.md / docs/*.md; None
    when no docs exist to check against."""
    root = Path(root)
    files = [root / "README.md"] + sorted((root / "docs").glob("*.md"))
    texts = [f.read_text() for f in files if f.exists()]
    if not texts:
        return None
    return set(_DOC_ENV_RE.findall("\n".join(texts)))


def _owner_ctor_name(call: ast.Call) -> str | None:
    """The THREAD_OWNER_CLASSES class name when ``call`` is its ctor (or
    a ``.start()`` chained onto one); None otherwise."""
    d = _dotted(call.func)
    for name in THREAD_OWNER_CLASSES:
        if d == name or d.endswith("." + name):
            return name
    if (
        isinstance(call.func, ast.Attribute)
        and call.func.attr == "start"
        and isinstance(call.func.value, ast.Call)
    ):
        return _owner_ctor_name(call.func.value)
    return None


def _is_sentinel_ctor(call: ast.Call) -> bool:
    """``RecompileSentinel(...)`` or a ``.start()`` chained onto one."""
    d = _dotted(call.func)
    if d.endswith("RecompileSentinel"):
        return True
    if (
        isinstance(call.func, ast.Attribute)
        and call.func.attr == "start"
        and isinstance(call.func.value, ast.Call)
    ):
        return _is_sentinel_ctor(call.func.value)
    return False


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    rule: str
    msg: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def _pragmas(source: str) -> dict:
    """line -> set of allowed rule ids (``*`` = all)."""
    out: dict = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _receiver_name(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a call target ('threading.Thread')."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str, env_surface=None):
        self.path = path
        self.rel = rel  # repo-relative path, for scope decisions
        self.pragmas = _pragmas(source)
        self.violations: list = []
        self._thread_classes: list = []  # ClassDef stack: is-Thread-subclass
        self._loop_depth = 0  # for/while nesting (trace-hot-emit)
        # (declared, documented) DLT_* name sets for env-surface, or None
        # when lint runs without repo-root context (rule off)
        self.env_surface = env_surface

    # -- plumbing -----------------------------------------------------------

    def _in_scope(self, packages) -> bool:
        parts = Path(self.rel).parts
        return any(p in parts for p in packages)

    def _allowed(self, rule: str, node: ast.AST) -> bool:
        start = getattr(node, "lineno", 0)
        end = getattr(node, "end_lineno", start) or start
        for line in range(start, end + 1):
            allowed = self.pragmas.get(line)
            if allowed and (rule in allowed or "*" in allowed):
                return True
        # a pragma-only line directly above the statement also applies
        allowed = self.pragmas.get(start - 1)
        return bool(allowed and (rule in allowed or "*" in allowed))

    def _flag(self, rule: str, node: ast.AST, msg: str):
        if not self._allowed(rule, node):
            self.violations.append(
                Violation(self.path, getattr(node, "lineno", 0), rule, msg)
            )

    # -- rules --------------------------------------------------------------

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if node.type is None:
            self._flag(
                "bare-except", node,
                "bare `except:` — name the exception (it catches "
                "KeyboardInterrupt/SystemExit and hides StallError)",
            )
        else:
            names = []
            types = node.type.elts if isinstance(node.type, ast.Tuple) else [node.type]
            for t in types:
                names.append(_receiver_name(t))
            body_is_noop = all(
                isinstance(s, ast.Pass)
                or (
                    isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                    and s.value.value is Ellipsis
                )
                for s in node.body
            )
            if body_is_noop and any(n in ("Exception", "BaseException") for n in names):
                self._flag(
                    "swallowed-exception", node,
                    "`except Exception: pass` swallows every failure mode — "
                    "narrow it, handle it, or pragma it with the reason",
                )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        # lock-with: explicit .acquire() on lock-ish receivers
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "acquire"
            and _LOCKISH_RE.search(_receiver_name(node.func.value))
        ):
            self._flag(
                "lock-with", node,
                f"explicit {_dotted(node.func)}() — locks are taken via "
                "`with` only (exception safety)",
            )
        # thread-daemon: Thread(...) constructors
        dotted = _dotted(node.func)
        if dotted in ("threading.Thread", "Thread"):
            if not any(kw.arg == "daemon" for kw in node.keywords):
                self._flag(
                    "thread-daemon", node,
                    "Thread(...) without an explicit daemon= — an "
                    "undeclared non-daemon thread hangs process exit",
                )
        # thread-daemon: Thread subclass super().__init__ without daemon=
        if (
            dotted.endswith("__init__")
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Call)
            and _dotted(node.func.value.func) == "super"
            and self._thread_classes
            and self._thread_classes[-1]
        ):
            if not any(kw.arg == "daemon" for kw in node.keywords):
                self._flag(
                    "thread-daemon", node,
                    "Thread subclass super().__init__ without daemon= — "
                    "declare the thread's lifetime explicitly",
                )
        # float64 dtype literal in device-side scope
        if self._in_scope(FLOAT64_SCOPE):
            for kw in node.keywords:
                if (
                    kw.arg == "dtype"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value in ("float64", "f8", "double")
                ):
                    self._flag(
                        "float64", kw.value,
                        "float64 dtype literal in a device-side package",
                    )
        # host-sync: potential blocking fetches in hot packages
        if self._in_scope(HOST_SYNC_SCOPE):
            if dotted in ("np.asarray", "np.array", "numpy.asarray",
                          "numpy.array", "jax.device_get"):
                self._flag(
                    "host-sync", node,
                    f"{dotted}(...) in a hot package is a potential "
                    "blocking device->host sync — pragma the sanctioned "
                    "sites (see docs/ANALYSIS.md)",
                )
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "memory_stats"
            ):
                self._flag(
                    "host-sync", node,
                    ".memory_stats() in a hot package is a device-runtime "
                    "round trip — only the cold-path HBM-ledger site "
                    "(runtime/profiling.py) is sanctioned; pragma it",
                )
        # trace-hot-emit: span emission discipline in emitting packages —
        # per-iteration .event() calls re-tuple name/keys every time and
        # invite dict construction; loops must use a pre-bound
        # Trace.bind(...) / Tracer.bind_global(...) emitter (one tuple
        # append per event)
        if (
            self._in_scope(TRACE_EMIT_SCOPE)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "event"
            and _TRACEISH_RE.match(_receiver_name(node.func.value) or "")
        ):
            if self._loop_depth > 0:
                self._flag(
                    "trace-hot-emit", node,
                    ".event(...) inside a loop in a hot package — bind a "
                    "pre-bound emitter outside the loop (Trace.bind) and "
                    "call it per iteration",
                )
            has_dict = any(
                isinstance(a, (ast.Dict, ast.DictComp)) for a in node.args
            ) or any(
                isinstance(kw.value, (ast.Dict, ast.DictComp))
                for kw in node.keywords
            )
            if has_dict:
                self._flag(
                    "trace-hot-emit", node,
                    "dict construction in a span emit call — pass scalar "
                    "vals against pre-bound keys instead",
                )
        # env-surface: DLT_* env reads must be on the declared /debug/config
        # surface and documented
        if dotted in ("os.environ.get", "environ.get", "os.getenv", "getenv"):
            if (
                node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("DLT_")
            ):
                self._check_env_surface(node.args[0].value, node)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        # env-surface: os.environ["DLT_X"] subscript reads
        if (
            _dotted(node.value) in ("os.environ", "environ")
            and isinstance(node.slice, ast.Constant)
            and isinstance(node.slice.value, str)
            and node.slice.value.startswith("DLT_")
        ):
            self._check_env_surface(node.slice.value, node)
        self.generic_visit(node)

    def _check_env_surface(self, var: str, node: ast.AST):
        if self.env_surface is None or not self._in_scope(ENV_SURFACE_SCOPE):
            return
        declared, documented = self.env_surface
        missing = []
        if declared is not None and var not in declared:
            missing.append(
                "api.py's DLT_ENV_SURFACE registry (the /debug/config "
                "declared knob surface)"
            )
        if documented is not None and var not in documented:
            missing.append("README/docs")
        if missing:
            self._flag(
                "env-surface", node,
                f"{var} is read here but missing from "
                f"{' and from '.join(missing)} — every DLT_* knob must be "
                "discoverable from a running replica and documented",
            )

    def _visit_loop(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = _visit_loop
    visit_While = _visit_loop

    def visit_Attribute(self, node: ast.Attribute):
        if self._in_scope(FLOAT64_SCOPE) and node.attr == "float64":
            self._flag(
                "float64", node,
                "float64 literal in a device-side package (one f64 "
                "constant promotes the whole chain)",
            )
        self.generic_visit(node)

    def visit_ClassDef(self, node: ast.ClassDef):
        is_thread = any(
            _dotted(b) in ("threading.Thread", "Thread") for b in node.bases
        )
        self._thread_classes.append(is_thread)
        if self._in_scope(SENTINEL_SCOPE):
            self._check_sentinel_release(node)
            self._check_thread_release(node)
        self.generic_visit(node)
        self._thread_classes.pop()

    @staticmethod
    def _walk_own(node):
        """ast.walk, but skipping nested ClassDef subtrees — a nested
        class's sentinel belongs to the nested class (visit_ClassDef
        checks it on its own visit), not to the enclosing one."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                continue
            yield child
            yield from _Linter._walk_own(child)

    def _check_sentinel_release(self, cls: ast.ClassDef):
        """sentinel-release: every ``self.<attr> = RecompileSentinel(...)``
        in this class must have a teardown method (close/stop/__exit__)
        that calls ``self.<attr>.stop()`` — the subscription is process-
        global and a leaked sealed sentinel outlives its engine."""
        holders: list = []
        for sub in self._walk_own(cls):
            if not (
                isinstance(sub, ast.Assign)
                and isinstance(sub.value, ast.Call)
                and _is_sentinel_ctor(sub.value)
            ):
                continue
            for tgt in sub.targets:
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    holders.append((tgt.attr, sub))
        if not holders:
            return
        released: set = set()
        for sub in cls.body:
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub.name in ("close", "stop", "__exit__", "__del__")
            ):
                for c in ast.walk(sub):
                    if (
                        isinstance(c, ast.Call)
                        and isinstance(c.func, ast.Attribute)
                        and c.func.attr in ("stop", "close")
                        and isinstance(c.func.value, ast.Attribute)
                        and isinstance(c.func.value.value, ast.Name)
                        and c.func.value.value.id == "self"
                    ):
                        released.add(c.func.value.attr)
        for attr, node in holders:
            if attr not in released:
                self._flag(
                    "sentinel-release", node,
                    f"self.{attr} subscribes a RecompileSentinel but no "
                    "close/stop/__exit__ method calls "
                    f"self.{attr}.stop() — a leaked sealed sentinel "
                    "outlives the engine and kills later engine builds",
                )

    def _check_thread_release(self, cls: ast.ClassDef):
        """thread-release: every ``self.<attr>`` holding a gateway-owned
        background loop (THREAD_OWNER_CLASSES, directly or via a local
        alias) must be released — ``self.<attr>.stop()`` (or
        ``.close()``/``.join()``) from a RELEASE_METHODS teardown. A
        leaked scraper/autoscaler/prober/peer-sync loop keeps actuating
        against the fleet after its gateway is gone — and doubles on an
        in-process restart."""
        # local aliases: x = FleetScraper(...), possibly .start()-chained
        aliases: set = set()
        for sub in self._walk_own(cls):
            if (
                isinstance(sub, ast.Assign)
                and isinstance(sub.value, ast.Call)
                and _owner_ctor_name(sub.value)
            ):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name):
                        aliases.add(tgt.id)
        holders: list = []
        for sub in self._walk_own(cls):
            if not isinstance(sub, ast.Assign):
                continue
            value = sub.value
            owner = None
            if isinstance(value, ast.Call):
                owner = _owner_ctor_name(value)
            elif isinstance(value, ast.Name) and value.id in aliases:
                owner = value.id
            if not owner:
                continue
            for tgt in sub.targets:
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    holders.append((tgt.attr, owner, sub))
        if not holders:
            return
        released: set = set()
        for sub in cls.body:
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub.name in RELEASE_METHODS
            ):
                for c in ast.walk(sub):
                    if (
                        isinstance(c, ast.Call)
                        and isinstance(c.func, ast.Attribute)
                        and c.func.attr in ("stop", "close", "join")
                        and isinstance(c.func.value, ast.Attribute)
                        and isinstance(c.func.value.value, ast.Name)
                        and c.func.value.value.id == "self"
                    ):
                        released.add(c.func.value.attr)
        for attr, owner, node in holders:
            if attr not in released:
                self._flag(
                    "thread-release", node,
                    f"self.{attr} holds a {owner} background loop but no "
                    "close/stop/shutdown/server_close method calls "
                    f"self.{attr}.stop() — a leaked control loop keeps "
                    "actuating against the fleet after its gateway dies",
                )


def lint_source(
    source: str, path: str, rel: str | None = None, env_surface=None
) -> list:
    tree = ast.parse(source, filename=path)
    linter = _Linter(
        path, rel if rel is not None else path, source, env_surface=env_surface
    )
    linter.visit(tree)
    return linter.violations


def lint_file(path, root=None, env_surface=None) -> list:
    p = Path(path)
    rel = str(p.relative_to(root)) if root else str(p)
    if env_surface is None and root is not None:
        env_surface = (declared_env_surface(root), documented_env_vars(root))
    return lint_source(p.read_text(), str(p), rel, env_surface=env_surface)


def lint_paths(paths, root=None, exclude=("tests", "__pycache__")) -> list:
    """Lint every .py under `paths` (files or directories). With a repo
    `root`, the cross-file env-surface context (DLT_ENV_SURFACE registry +
    docs) is resolved ONCE and shared across every file."""
    env_surface = None
    if root is not None:
        env_surface = (declared_env_surface(root), documented_env_vars(root))
    out: list = []
    for path in paths:
        p = Path(path)
        files = [p] if p.is_file() else sorted(p.rglob("*.py"))
        for f in files:
            if any(part in exclude for part in f.parts):
                continue
            out.extend(lint_file(f, root=root, env_surface=env_surface))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="dlt-lint", description=__doc__)
    ap.add_argument("paths", nargs="*", help="files/dirs (default: the package + scripts)")
    ap.add_argument("--root", default=None, help="repo root for scope-relative paths")
    args = ap.parse_args(argv)

    root = Path(args.root) if args.root else Path(__file__).resolve().parents[2]
    paths = [Path(p) for p in args.paths] or [
        root / "distributed_llama_tpu",
        root / "scripts",
        root / "launch.py",
    ]
    violations = lint_paths([p for p in paths if p.exists()], root=root)
    for v in violations:
        print(v)
    if violations:
        print(f"dlt-lint: {len(violations)} violation(s)")
        return 1
    print("dlt-lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
