"""Correctness-tooling tests: reprolint rules + sanitizer rails.

Layer 1 (``tools.analysis.reprolint``) is pinned by a known-bad fixture
corpus: every rule must flag a distilled reproduction of the historical
bug it encodes AND stay silent on the fixed twin — so a rule can neither
rot (stops firing) nor creep (starts firing on the sanctioned idiom).

Layer 2 (``tools.analysis.sanitize``) is pinned from both sides: a
seeded random-op property test proves the shadow page model agrees with
a healthy allocator, and injected corruptions (double-alloc of a live
page, free-while-shared, hot+cold residency) prove divergence is caught
loudly.  The end-to-end test runs a real overlapped+tiered+prefix-cache
engine under ``REPRO_SANITIZE=1`` and asserts the rails ran clean.
"""

import random
import textwrap

import pytest

from tools.analysis import sanitize
from tools.analysis.reprolint import run as lint_run


def _lint(tmp_path, code, rule, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    findings, errors = lint_run([str(f)], select=[rule])
    assert not errors, errors
    return findings


# ======================================================================
# Layer 1: the known-bad / known-good fixture corpus
# ======================================================================
def test_async_aliasing_flags_uncopied_host_buffer(tmp_path):
    bad = """
        class E:
            def round(self):
                tok, cache = self._decode_sample(
                    self.params, self.last_np, tok_dev,
                    {**self.cache, "block": self.block})
    """
    found = _lint(tmp_path, bad, "async-aliasing")
    assert {f.message.split("`")[1] for f in found} == {
        "self.last_np", "self.block"}


def test_async_aliasing_accepts_copied_buffer(tmp_path):
    good = """
        class E:
            def round(self):
                tok, cache = self._decode_sample(
                    self.params, self.last_np.copy(), tok_dev,
                    {**self.cache, "block": self.block.copy()})
    """
    assert _lint(tmp_path, good, "async-aliasing") == []


def test_boolean_select_trap_flags_numeric_and_sentinel(tmp_path):
    bad = """
        _NO_BUDGET = 1 << 30
        def f(arrival_s, chunk):
            t = (arrival_s or 0.0) + 1.0
            budget = chunk or _NO_BUDGET
            return t, budget
    """
    found = _lint(tmp_path, bad, "boolean-select-trap")
    assert len(found) == 2


def test_boolean_select_trap_flags_and_or_chain(tmp_path):
    found = _lint(tmp_path, "y = cond and a or b\n", "boolean-select-trap")
    assert len(found) == 1 and "a and b or c" in found[0].message


def test_boolean_select_trap_accepts_truth_tests_and_none_check(tmp_path):
    good = """
        def f(x, flags):
            if x or 0:              # truth test: no value escapes
                pass
            while flags or 0:
                break
            v = 0.0 if x is None else x
            d = flags or {}         # result-equivalent default: fine
            return v, d
    """
    assert _lint(tmp_path, good, "boolean-select-trap") == []


def test_boolean_select_trap_pragma_suppresses(tmp_path):
    code = """
        def f(x):
            # reprolint: ok boolean-select-trap — 0 is not a valid x here
            return x or 1000
    """
    assert _lint(tmp_path, code, "boolean-select-trap") == []


def test_donation_use_after_flags_stale_read(tmp_path):
    bad = """
        import jax
        step = jax.jit(fn, donate_argnums=(1,))
        def loop(params, cache):
            out, new_cache = step(params, cache)
            return cache["k"]   # stale: cache was donated to step()
    """
    found = _lint(tmp_path, bad, "donation-use-after")
    assert len(found) == 1 and "`cache`" in found[0].message


def test_donation_use_after_accepts_rebind(tmp_path):
    good = """
        import jax
        step = jax.jit(fn, donate_argnums=(1,))
        def loop(params, cache):
            out, cache = step(params, cache)
            return cache["k"]   # rebound: reads the NEW buffer
    """
    assert _lint(tmp_path, good, "donation-use-after") == []


def test_wire_field_drift_flags_both_directions(tmp_path):
    (tmp_path / "proj" / "fleet").mkdir(parents=True)
    (tmp_path / "proj" / "fleet" / "wire.py").write_text(textwrap.dedent("""
        WIRE_FIELDS = {"Thing": ("a", "ghost")}
    """))
    (tmp_path / "proj" / "models.py").write_text(textwrap.dedent("""
        import dataclasses
        @dataclasses.dataclass
        class Thing:
            a: int
            b: int = 0
    """))
    findings, errors = lint_run([str(tmp_path / "proj")],
                                select=["wire-field-drift"])
    assert not errors
    msgs = "\n".join(f.message for f in findings)
    assert "field `b` of Thing is missing" in msgs
    assert "`Thing.ghost`" in msgs and "stale" in msgs


def test_wire_field_drift_clean_when_in_sync(tmp_path):
    (tmp_path / "proj" / "fleet").mkdir(parents=True)
    (tmp_path / "proj" / "fleet" / "wire.py").write_text(
        'WIRE_FIELDS = {"Thing": ("a", "b")}\n')
    (tmp_path / "proj" / "models.py").write_text(textwrap.dedent("""
        import dataclasses
        @dataclasses.dataclass
        class Thing:
            a: int
            b: int = 0
    """))
    findings, _ = lint_run([str(tmp_path / "proj")],
                           select=["wire-field-drift"])
    assert findings == []


def test_wire_field_drift_flags_missing_manifest(tmp_path):
    (tmp_path / "proj" / "fleet").mkdir(parents=True)
    (tmp_path / "proj" / "fleet" / "wire.py").write_text("TAGS = {}\n")
    findings, _ = lint_run([str(tmp_path / "proj")],
                           select=["wire-field-drift"])
    assert len(findings) == 1 and "no WIRE_FIELDS manifest" in \
        findings[0].message


def test_nondeterminism_flags_hot_path_only(tmp_path):
    bad = """
        import numpy as np, time, jax
        def sample():
            noise = np.random.rand(4)
            t0 = time.time()
            key = jax.random.PRNGKey(int(time.time()))
            return noise, t0, key
    """
    # same code, hot path vs elsewhere; the PRNGKey line yields two
    # findings (the embedded time.time() call AND the tainted seed)
    hot = _lint(tmp_path, bad, "nondeterminism",
                filename="src/repro/serving/x.py")
    cold = _lint(tmp_path, bad, "nondeterminism", filename="bench/x.py")
    assert len(hot) == 4 and cold == []
    assert any("np.random" in f.message for f in hot)
    assert any("seeded from nondeterministic" in f.message for f in hot)


def test_nondeterminism_accepts_seeded_and_monotonic(tmp_path):
    good = """
        import time, jax
        def sample(seed, i):
            t0 = time.monotonic()
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            return t0, key
    """
    assert _lint(tmp_path, good, "nondeterminism",
                 filename="src/repro/serving/x.py") == []


def test_jit_in_loop_flags_and_accepts_hoisted(tmp_path):
    bad = """
        import jax
        def serve(steps):
            for _ in range(steps):
                f = jax.jit(body)     # recompiles every iteration
                f(x)
    """
    good = """
        import jax
        f = jax.jit(body)
        def serve(steps):
            for _ in range(steps):
                f(x)
    """
    assert len(_lint(tmp_path, bad, "jit-in-loop")) == 1
    assert _lint(tmp_path, good, "jit-in-loop", "good.py") == []


def test_mutable_default_flags_display_and_ctor(tmp_path):
    bad = """
        import numpy as np
        def f(acc=[], buf=np.zeros(4)):
            return acc, buf
    """
    good = """
        def f(acc=None, buf=()):
            acc = [] if acc is None else acc
            return acc, buf
    """
    assert len(_lint(tmp_path, bad, "mutable-default")) == 2
    assert _lint(tmp_path, good, "mutable-default", "good.py") == []


def test_silent_except_flags_bare_and_broad_pass(tmp_path):
    bad = """
        def f():
            try:
                g()
            except:
                pass
        def h():
            try:
                g()
            except Exception:
                pass
    """
    good = """
        def f(log):
            try:
                g()
            except OSError:
                pass            # narrow best-effort close: accepted
            try:
                g()
            except Exception as e:
                log.warning(e)  # recorded: accepted
    """
    assert len(_lint(tmp_path, bad, "silent-except")) == 2
    assert _lint(tmp_path, good, "silent-except", "good.py") == []


def test_lint_reports_parse_errors(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    findings, errors = lint_run([str(tmp_path)])
    assert len(errors) == 1 and "broken.py" in errors[0]


def test_repo_tree_is_clean():
    """The merged tree lints clean — the acceptance gate CI enforces."""
    findings, errors = lint_run(["src", "tests"])
    assert errors == []
    assert findings == [], "\n".join(f.render() for f in findings)


# ======================================================================
# Layer 2: sanitizer rails
# ======================================================================
@pytest.fixture(autouse=True)
def _fresh_counters():
    sanitize.reset_counters()
    yield
    sanitize.reset_counters()


def _shadowed_alloc(num_pages):
    from repro.serving.kv_cache import PageAllocator
    a = PageAllocator(num_pages)
    sanitize.attach_page_shadow(a)
    return a


def test_shadow_model_agrees_with_healthy_allocator():
    """Property test: a contract-respecting random op sequence never trips
    the shadow, and the real/model views stay identical throughout."""
    rng = random.Random(1234)
    a = _shadowed_alloc(32)
    live = []          # pages with refcount >= 1
    parked = []        # refcount 0 (idle cached): still freeable
    for _ in range(400):
        op = rng.choice(["alloc", "free", "incref", "decref"])
        if op == "alloc" and a.available:
            live += a.alloc(rng.randint(1, min(3, a.available)))
        elif op == "free" and (live or parked):
            src = live if (live and (not parked or rng.random() < 0.7)) \
                else parked
            p = src.pop(rng.randrange(len(src)))
            if src is live and a.refcount(p) > 1:
                a.decref(p)      # drop sharers first, as the engine does
                live.append(p)
                continue
            a.free([p])
        elif op == "incref" and live:
            a.incref(rng.choice(live))
        elif op == "decref" and live:
            p = live[rng.randrange(len(live))]
            if a.decref(p) == 0:
                live.remove(p)
                parked.append(p)
    assert sanitize.report_count() == 0
    assert sanitize.check_count() > 0
    assert a.available == len(a._shadow.free)


def test_shadow_model_catches_double_alloc_of_live_page():
    """Inject the double-free bug class: the free list hands out a page
    that is still live.  The real allocator trusts its (corrupted) free
    list; the shadow does not."""
    a = _shadowed_alloc(8)
    p = a.alloc(1)[0]
    a._free.append(p)          # simulated free-list corruption
    a._free_set.add(p)
    with pytest.raises(sanitize.SanitizerError, match="already live"):
        a.alloc(8 - 1)         # pops the corrupted entry eventually
    assert sanitize.report_count() == 1


def test_shadow_model_catches_free_while_shared():
    """Inject a refcount undercount: the real allocator thinks the page
    has one owner and accepts the free; the shadow knows a sharer
    remains."""
    a = _shadowed_alloc(8)
    p = a.alloc(1)[0]
    a.incref(p)                # two sharers (model refs = 2)
    a._refs[p] = 1             # simulated refcount corruption
    with pytest.raises(sanitize.SanitizerError, match="freed while shared"):
        a.free([p])
    assert sanitize.report_count() == 1


def test_tier_shadow_catches_hot_and_cold_residency():
    """``store`` of a key that is still eviction-marked hot: the real
    tier accepts it (store does not consult the eviction queue); the
    shadow flags the double residency."""
    from repro.serving.kv_cache import TieredPageAllocator
    t = TieredPageAllocator(8, flash_pages=4)
    sanitize.attach_page_shadow(t.hot)
    sanitize.attach_tier_shadow(t)
    t.mark_evictable(("s", 0), 1)
    with pytest.raises(sanitize.SanitizerError, match="hot\\+cold"):
        t.store(("s", 0), b"payload")
    assert sanitize.report_count() == 1


def test_tier_shadow_clean_on_spill_prefetch_cycle():
    from repro.serving.kv_cache import TieredPageAllocator
    t = TieredPageAllocator(8, flash_pages=4)
    sanitize.attach_page_shadow(t.hot)
    sanitize.attach_tier_shadow(t)
    pids = t.alloc(2)
    for i, p in enumerate(pids):
        t.mark_evictable(("s", i), p)
    popped = t.pop_evictable(2)
    for (key, pid) in popped:
        t.store(key, f"blob{pid}".encode())
        t.free([pid])
    for key, _pid in popped:           # prefetch back
        assert t.fetch(key).startswith(b"blob")
    t.drop_slot(lambda k: k[0] == "s")
    assert sanitize.report_count() == 0
    assert sanitize.check_count() > 0


def test_dispatch_guard_catches_mutated_arg():
    import numpy as np
    buf = np.arange(8, dtype=np.int32)
    ok = sanitize.guard_dispatch(0, last_np=buf.copy())
    sanitize.check_drain(ok)           # untouched copy: clean
    racy = sanitize.guard_dispatch(1, last_np=buf)
    buf[3] = 99                        # host mutates while step in flight
    with pytest.raises(sanitize.SanitizerError, match="last_np"):
        sanitize.check_drain(racy)


def test_retrace_budget():
    class Fake:
        def __init__(self, n):
            self._cache_size = lambda: n
    sanitize.check_retrace(Fake(3), "ok", budget=8)
    with pytest.raises(sanitize.SanitizerError, match="retrace budget"):
        sanitize.check_retrace(Fake(9), "hot", budget=8)
    sanitize.check_retrace(object(), "no-surface", budget=1)  # no-op


def test_wire_manifest_runtime_check():
    from repro.serving.core import Request, RequestOutput, SlotSnapshot
    from repro.serving.fleet.wire import WIRE_FIELDS
    from repro.serving.scheduler import SamplingParams
    classes = {"Request": Request, "SamplingParams": SamplingParams,
               "RequestOutput": RequestOutput, "SlotSnapshot": SlotSnapshot}
    sanitize.check_wire_manifest(WIRE_FIELDS, classes)   # in sync today
    pruned = dict(WIRE_FIELDS)
    pruned["Request"] = WIRE_FIELDS["Request"][:-1]
    with pytest.raises(sanitize.SanitizerError, match="not covered"):
        sanitize.check_wire_manifest(pruned, classes)


# ======================================================================
# end-to-end: a real engine under REPRO_SANITIZE=1
# ======================================================================
def test_sanitized_engine_matches_plain_engine(monkeypatch):
    """Overlapped + tiered + prefix-cache decode with every rail armed:
    zero reports, rails demonstrably exercised, and the token streams
    bit-identical to an un-sanitized sync engine."""
    import jax
    from repro.configs.registry import ASSIGNED_ARCHS
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), max_seq=64)

    def serve(**kw):
        reqs = [Request(rid=i, prompt=[2 + i, 5], max_new_tokens=6)
                for i in range(3)]
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=48,
                            eos_id=-1, page_size=8, **kw)
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.out_tokens for r in reqs]

    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    baseline = serve()
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    sanitize.reset_counters()
    sanitized = serve(overlap=True, kv_tier="flash", num_pages=6,
                      prefix_cache=True)
    assert sanitized == baseline
    assert sanitize.report_count() == 0
    assert sanitize.check_count() > 0
