"""Repo lint tests: every rule fires on a synthetic offender, pragmas
suppress, and — the dogfood criterion — the real tree lints clean."""

from pathlib import Path

import pytest

from distributed_llama_tpu.analysis import lint

pytestmark = pytest.mark.analysis

ROOT = Path(__file__).resolve().parents[1]


def _rules(src, rel="runtime/x.py"):
    return sorted({v.rule for v in lint.lint_source(src, "x.py", rel)})


def test_bare_except_flagged():
    assert _rules("try:\n    x = 1\nexcept:\n    x = 2\n") == ["bare-except"]


def test_swallowed_exception_flagged():
    src = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert _rules(src) == ["swallowed-exception"]
    # a handler that DOES something is fine
    src2 = "try:\n    x = 1\nexcept Exception:\n    x = 2\n"
    assert _rules(src2) == []
    # narrow types may pass-swallow (OSError cleanup idiom)
    src3 = "try:\n    x = 1\nexcept OSError:\n    pass\n"
    assert _rules(src3) == []


def test_lock_with_flagged_only_for_lockish_receivers():
    assert _rules("self._lock.acquire()\n") == ["lock-with"]
    assert _rules("self.cond.acquire()\n") == ["lock-with"]
    # Balancer.acquire() is an API method, not a lock acquire
    assert _rules("idx = balancer.acquire(exclude=tried)\n") == []


def test_thread_daemon_flagged():
    src = "import threading\nt = threading.Thread(target=f)\n"
    assert _rules(src) == ["thread-daemon"]
    ok = "import threading\nt = threading.Thread(target=f, daemon=True)\n"
    assert _rules(ok) == []
    sub = (
        "import threading\n"
        "class W(threading.Thread):\n"
        "    def __init__(self):\n"
        "        super().__init__()\n"
    )
    assert _rules(sub) == ["thread-daemon"]
    sub_ok = (
        "import threading\n"
        "class W(threading.Thread):\n"
        "    def __init__(self):\n"
        "        super().__init__(daemon=True)\n"
    )
    assert _rules(sub_ok) == []


def test_float64_scoped_to_device_packages():
    src = "import numpy as np\nx = np.zeros(4, dtype=np.float64)\n"
    assert _rules(src, "ops/x.py") == ["float64"]
    assert _rules(src, "converter/x.py") == []  # host-side package: fine
    lit = "x = np.zeros(4, dtype='float64')\n"
    assert _rules(lit, "models/x.py") == ["float64"]


def test_host_sync_scoped_to_hot_packages():
    src = "import numpy as np\nh = np.asarray(toks)\n"
    assert _rules(src, "runtime/x.py") == ["host-sync"]
    assert _rules(src, "parallel/x.py") == ["host-sync"]
    assert _rules(src, "server/x.py") == []  # server is not a hot package


def test_memory_stats_is_a_host_sync():
    """`.memory_stats()` is a device-runtime round trip: flagged in the hot
    packages, sanctioned only behind a pragma (the HBM-ledger site in
    runtime/profiling.py), fine in host-side packages."""
    src = "s = d.memory_stats()\n"
    assert _rules(src, "runtime/x.py") == ["host-sync"]
    assert _rules(src, "parallel/x.py") == ["host-sync"]
    assert _rules(src, "server/x.py") == []
    ok = "s = d.memory_stats()  # dlt: allow(host-sync) — cold-path ledger\n"
    assert _rules(ok, "runtime/x.py") == []


def test_trace_hot_emit_scoped_to_hot_packages():
    """Per-iteration span emission in runtime loops must ride a pre-bound
    emitter (runtime/tracing.py Emitter): `.event(...)` in a loop body —
    or a dict literal in any emit call — is flagged; the bound-emitter
    idiom and cold-path `.event(...)` calls pass."""
    in_loop = "for i in range(8):\n    tr.event('decode', 1, 2)\n"
    assert _rules(in_loop) == ["trace-hot-emit"]
    while_loop = "while go:\n    TRACER.event('x', 1)\n"
    assert _rules(while_loop) == ["trace-hot-emit"]
    # the sanctioned idiom: bind outside, tuple-append inside
    bound = "em = tr.bind('decode', ('n',))\nfor i in range(8):\n    em(1, 2, i)\n"
    assert _rules(bound) == []
    # cold-path (non-loop) events are fine
    cold = "tr.event('request', 1, 2)\n"
    assert _rules(cold) == []
    # dict construction in an emit call is flagged even outside loops
    dict_arg = "tr.event('x', 1, 2, {'a': 1})\n"
    assert _rules(dict_arg) == ["trace-hot-emit"]
    # the server package joined the emit scope with the goodput-ledger /
    # batch-timeline sites (PR 9): the Batcher step loop and the gateway
    # retry loop are per-iteration emitters too
    assert _rules(in_loop, "server/x.py") == ["trace-hot-emit"]
    assert _rules(dict_arg, "server/x.py") == ["trace-hot-emit"]
    # the sanctioned idioms pass in server scope: pre-bound emitters
    # (Trace.bind / Tracer.bind_global) and pragma'd once-per-request sites
    bound_global = (
        "em = TRACER.bind_global('batch_step', ('n',))\n"
        "while go:\n    em(1, 2, 3)\n"
    )
    assert _rules(bound_global, "server/x.py") == []
    pragma = (
        "while go:\n"
        "    tr.event('queue_wait', 1, 2)  # dlt: allow(trace-hot-emit)\n"
    )
    assert _rules(pragma, "server/x.py") == []
    # the router's per-request decision path (server/router.py, PR 10)
    # rides the same server-package scope: a per-iteration emit in it is
    # flagged exactly like the Batcher/gateway loops
    assert _rules(in_loop, "server/router.py") == ["trace-hot-emit"]
    assert _rules(bound, "server/router.py") == []
    # the fleet control plane's modules (PR 12: scheduler admission/
    # preemption loops, autoscaler ticks, the load twin's stub decode
    # loop) are server-scope too — hot-loop emits must stay pre-bound
    for mod in ("server/scheduler.py", "server/autoscaler.py",
                "server/loadtwin.py"):
        assert _rules(in_loop, mod) == ["trace-hot-emit"]
        assert _rules(bound, mod) == []
    # the KV movement layer (PR 13: transport fetch loops, per-segment
    # extract/insert loops) rides the runtime-package scope
    assert _rules(in_loop, "runtime/kv_transport.py") == ["trace-hot-emit"]
    assert _rules(bound, "runtime/kv_transport.py") == []
    # formats/ops stay out of scope
    assert _rules(in_loop, "formats/x.py") == []
    # non-trace receivers named `event` are not span emits
    other = "for i in range(8):\n    bus.event('x')\n"
    assert _rules(other) == []


def test_pragma_suppresses_same_line_and_line_above():
    same = "try:\n    x = 1\nexcept Exception:  # dlt: allow(swallowed-exception) — reason\n    pass\n"
    assert _rules(same) == []
    above = (
        "import threading\n"
        "# dlt: allow(thread-daemon)\n"
        "t = threading.Thread(target=f)\n"
    )
    assert _rules(above) == []
    wrong_rule = "try:\n    x = 1\nexcept Exception:  # dlt: allow(float64)\n    pass\n"
    assert _rules(wrong_rule) == ["swallowed-exception"]


def test_repo_tree_is_clean():
    """The dogfood criterion: scripts/dlt_lint.py exits 0 on the tree."""
    paths = [
        ROOT / "distributed_llama_tpu",
        ROOT / "scripts",
        ROOT / "launch.py",
    ]
    violations = lint.lint_paths([p for p in paths if p.exists()], root=ROOT)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_sentinel_release_requires_teardown_stop():
    bad = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.sentinel = RecompileSentinel(stats=s).start()\n"
    )
    assert _rules(bad) == ["sentinel-release"]
    # a close() releasing the subscription satisfies the rule
    ok = bad + (
        "    def close(self):\n"
        "        if self.sentinel is not None:\n"
        "            self.sentinel.stop()\n"
    )
    assert _rules(ok) == []
    # the bare (un-started) constructor is a subscription-to-be: same rule
    bare = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.guard = RecompileSentinel()\n"
    )
    assert _rules(bare) == ["sentinel-release"]
    # releasing a DIFFERENT attribute does not count
    wrong = bad + (
        "    def close(self):\n"
        "        self.other.stop()\n"
    )
    assert _rules(wrong) == ["sentinel-release"]
    # scope: device/server lifecycles only — a scripts/ helper is exempt
    assert _rules(bad, rel="scripts/x.py") == []
    # pragma suppresses at the assignment site
    sup = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.sentinel = RecompileSentinel().start()  # dlt: allow(sentinel-release)\n"
    )
    assert _rules(sup) == []
    # a NESTED class's sentinel belongs to the nested class: the outer
    # class must not be flagged for it (and the inner one, which releases
    # correctly, is clean on its own visit)
    nested_ok = (
        "class Outer:\n"
        "    class Inner:\n"
        "        def __init__(self):\n"
        "            self.s = RecompileSentinel().start()\n"
        "        def close(self):\n"
        "            self.s.stop()\n"
    )
    assert _rules(nested_ok) == []


def test_thread_release_covers_gateway_owned_loops():
    """The sentinel-release rule's thread edition (ISSUE 15): a class
    holding a FleetScraper/Autoscaler/HealthProber/GatewayPeering without
    a teardown releasing it is the exact leak class the gateway restart
    tests would instantiate twice."""
    bad = (
        "class Gw:\n"
        "    def __init__(self, bal):\n"
        "        self.scraper = FleetScraper(bal).start()\n"
    )
    assert _rules(bad, rel="server/x.py") == ["thread-release"]
    # releasing from any teardown name (incl. the http.server pair)
    ok = bad + (
        "    def server_close(self):\n"
        "        self.scraper.stop()\n"
    )
    assert _rules(ok, rel="server/x.py") == []
    # the local-alias form must not evade the rule (the GatewayServer
    # shape: build first, attach conditionally)
    aliased_bad = (
        "class Gw:\n"
        "    def start(self, bal):\n"
        "        scraper = FleetScraper(bal)\n"
        "        self._scraper = scraper\n"
    )
    assert _rules(aliased_bad, rel="server/x.py") == ["thread-release"]
    aliased_ok = aliased_bad + (
        "    def shutdown(self):\n"
        "        if self._scraper is not None:\n"
        "            self._scraper.stop()\n"
    )
    assert _rules(aliased_ok, rel="server/x.py") == []
    # a prober joined (its loop stops via a shared event) counts released
    prober = (
        "class Gw:\n"
        "    def __init__(self, bal, stop):\n"
        "        self._prober = HealthProber(bal, stop)\n"
        "    def shutdown(self):\n"
        "        self._prober.join(timeout=5)\n"
    )
    assert _rules(prober, rel="server/x.py") == []
    # releasing a DIFFERENT attribute does not count
    wrong = bad + (
        "    def close(self):\n"
        "        self.other.stop()\n"
    )
    assert _rules(wrong, rel="server/x.py") == ["thread-release"]
    # scope: server/runtime lifecycles — a scripts/ helper is exempt
    assert _rules(bad, rel="scripts/x.py") == []
    # pragma suppresses at the assignment site
    sup = (
        "class Gw:\n"
        "    def __init__(self, bal):\n"
        "        self.a = Autoscaler(bal)  # dlt: allow(thread-release)\n"
    )
    assert _rules(sup, rel="server/x.py") == []


# --------------------------------------------------------------------------
# env-surface: DLT_* reads must be on the declared /debug/config surface
# --------------------------------------------------------------------------

_SURFACE = ({"DLT_DECLARED"}, {"DLT_DECLARED", "DLT_DOC_ONLY"})


def _env_rules(src, env_surface=_SURFACE, rel="distributed_llama_tpu/runtime/x.py"):
    return lint.lint_source(src, "x.py", rel, env_surface=env_surface)


def test_env_surface_flags_undeclared_read():
    src = 'import os\nv = os.environ.get("DLT_FAKE_KNOB")\n'
    vio = _env_rules(src)
    assert [v.rule for v in vio] == ["env-surface"]
    # the message names the offending variable and both missing surfaces
    assert "DLT_FAKE_KNOB" in vio[0].msg
    assert "DLT_ENV_SURFACE" in vio[0].msg
    assert "README/docs" in vio[0].msg


def test_env_surface_all_read_forms_are_seen():
    getenv = 'import os\nv = os.getenv("DLT_FAKE_KNOB", "0")\n'
    sub = 'import os\nv = os.environ["DLT_FAKE_KNOB"]\n'
    from_import = 'from os import environ\nv = environ.get("DLT_FAKE_KNOB")\n'
    for src in (getenv, sub, from_import):
        assert [v.rule for v in _env_rules(src)] == ["env-surface"], src


def test_env_surface_declared_and_documented_is_clean():
    src = 'import os\nv = os.environ.get("DLT_DECLARED")\n'
    assert _env_rules(src) == []
    # documented-but-undeclared still flags (registry is the API surface)
    doc_only = 'import os\nv = os.environ.get("DLT_DOC_ONLY")\n'
    vio = _env_rules(doc_only)
    assert [v.rule for v in vio] == ["env-surface"]
    assert "README/docs" not in vio[0].msg


def test_env_surface_scope_pragma_and_missing_context():
    src = 'import os\nv = os.environ.get("DLT_FAKE_KNOB")\n'
    # non-DLT vars and out-of-package files are not the lint's business
    assert _env_rules('import os\nv = os.environ.get("HOME")\n') == []
    assert _env_rules(src, rel="scripts/x.py") == []
    # rule is off when no env-surface context could be resolved
    assert _env_rules(src, env_surface=None) == []
    sup = (
        "import os\n"
        'v = os.environ.get("DLT_FAKE_KNOB")  # dlt: allow(env-surface)\n'
    )
    assert _env_rules(sup) == []


def test_env_surface_registry_resolves_from_repo():
    """declared_env_surface parses the literal registry out of server/api.py
    and documented_env_vars sweeps README + docs; both must cover the knobs
    the tree actually reads (the repo-clean test proves the closure)."""
    declared = lint.declared_env_surface(ROOT)
    documented = lint.documented_env_vars(ROOT)
    assert declared is not None and "DLT_KV_LAYOUT" in declared
    assert documented is not None and declared <= documented, (
        "declared knobs missing from docs: "
        f"{sorted(declared - documented)}"
    )
