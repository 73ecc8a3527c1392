"""The analysis toolkit's own test suite (PR 8).

Three surfaces:

  * the AST linter (tools/analysis/lint + passes/): one seeded
    violation per pass is detected, the clean twin of each snippet is
    not, suppressions work and are themselves audited;
  * the runtime witnesses (lockgraph, leakwitness): an ABBA lock-order
    inversion is flagged as a cycle even though no deadlock fired,
    Condition interop keeps the held-set honest, and the leak helpers
    catch a capability grant that outlives its op;
  * the repo itself: the full scoped lint run is clean (the CI gate),
    and the counter registry matches the live Stats dataclasses.
"""
import textwrap
import threading

import pytest

from tools.analysis import leakwitness, lockgraph
from tools.analysis.lint import lint_paths, lint_source, repo_root, \
    scoped_files
from tools.analysis.passes import counters as counters_pass


def _lint(body, passes=None, **kw):
    return lint_source(textwrap.dedent(body), passes=passes, **kw)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# one seeded violation per pass; each snippet's clean twin stays silent


def test_lifecycle_flags_unpaired_acquire():
    bad = _lint("""
        def stage(self, k):
            slots = self.ring.acquire(k)
            self.fill(k)
    """, passes=["lifecycle"])
    assert _rules(bad) == ["lifecycle"]
    assert "acquire" in bad[0].msg


def test_lifecycle_accepts_pairing_with_and_escape():
    clean = _lint("""
        def staged(self, k):
            with self.ring.acquire(k):
                self.fill(k)

        def sibling(self, k):
            slots = self.ring.acquire(k)
            try:
                self.fill(k)
            finally:
                self.ring.release(slots)

        def handoff(self, k):
            lease = self.ring.acquire(k)
            self._blocks.append(lease)      # ownership transferred

        def stored_receiver(self):
            self.lease.pin()                # receiver is tracked state
    """, passes=["lifecycle"])
    assert clean == []


def test_lifecycle_flags_statement_inside_leak_window():
    # a statement between the acquire and its try reopens the window
    bad = _lint("""
        def stage(self, k):
            slots = self.ring.acquire(k)
            self.log("acquired")
            try:
                self.fill(k)
            finally:
                self.ring.release(slots)
    """, passes=["lifecycle"])
    assert _rules(bad) == ["lifecycle"]


def test_lifecycle_flags_abandoned_submit_handle():
    bad = _lint("""
        def prefetch(self, fd, size, off):
            self.client.submit_pread(fd, size, off)
            self.steps += 1
    """, passes=["lifecycle"])
    assert _rules(bad) == ["lifecycle"]
    assert "completion handle" in bad[0].msg


def test_lifecycle_accepts_reaped_or_handed_off_submits():
    clean = _lint("""
        def read_sync(self, fd, size, off):
            return self.client.submit_pread(fd, size, off).wait()

        def read_windowed(self, plan):
            window = []
            for fd, size, off in plan:
                window.append(self.client.submit_pread(fd, size, off))
            return [h.wait() for h in window]

        def read_named(self, fd, size, off):
            h = self.client.submit_pread(fd, size, off)
            self.touch()
            return h.wait()

        def read_cancelled(self, fd, size, off):
            h = self.client.submit_pread(fd, size, off)
            try:
                self.touch()
            finally:
                h.cancel()

        def submit_pread_far(self, fd, size, off):
            return self.client.submit_pread(fd, size, off)
    """, passes=["lifecycle"])
    assert clean == []


def test_timeouts_flags_literals_and_accepts_policy():
    bad = _lint("""
        import time

        def wait_for_cqe(self):
            time.sleep(0.5)
            self._q.get(timeout=3.0)
            self._cv.wait(0.05)

        def poll(self, timeout=5.0):
            pass
    """, passes=["timeout-literal"])
    assert _rules(bad) == ["timeout-literal"] * 4
    clean = _lint("""
        import time

        def wait_for_cqe(self):
            time.sleep(self.timeouts.poll_interval_s)
            self._q.get(timeout=self.timeouts.poll_interval_s)
            time.sleep(self.timeouts.backoff(attempt + 2, salt=step))

        def poll(self, timeout=None):
            timeout = self.timeouts.dpu_tag_s if timeout is None \\
                else timeout
    """, passes=["timeout-literal"])
    assert clean == []


def test_counters_flags_undeclared_recovery_path_and_stats_field():
    bad = _lint("""
        def recover(self):
            note_recovery(self.faults, "transport.rety")   # typo
            self.stats.bogus_reads += 1
    """, passes=["counter"])
    assert _rules(bad) == ["counter", "counter"]
    msgs = " / ".join(f.msg for f in bad)
    assert "transport.rety" in msgs
    assert "bogus_reads" in msgs
    clean = _lint("""
        def recover(self):
            note_recovery(self.faults, "transport.retry")
            self.stats.reads += 1
    """, passes=["counter"])
    assert clean == []


def test_counters_flags_undeclared_section_in_data_path_counters():
    bad = _lint("""
        def data_path_counters(self):
            out = {"transport": {"reads": 1, "not_a_key": 2}}
            out["no_such_section"] = {"x": 1}
            return out
    """, passes=["counter"])
    msgs = " / ".join(f.msg for f in bad)
    assert "transport.not_a_key" in msgs
    assert "no_such_section" in msgs


def test_counters_flags_undeclared_span_name():
    bad = _lint("""
        def write(self, op):
            with tracing.span("ros2.ec.wirte", op=op):   # typo
                pass
            with tracing.span(self.name):                # not a literal
                pass
    """, passes=["counter"])
    assert _rules(bad) == ["counter", "counter"]
    msgs = " / ".join(f.msg for f in bad)
    assert "ros2.ec.wirte" in msgs and "string literal" in msgs
    clean = _lint("""
        def write(self, op):
            with tracing.span("ros2.ec.write", op=op):
                pass
    """, passes=["counter"])
    assert clean == []


def test_counters_finalize_flags_a_declared_span_never_emitted():
    counters_pass._seen_paths.clear()
    counters_pass._seen_spans.clear()
    emitting_all_but_one = "\n".join(
        f"tracing.span({name!r})"
        for name in sorted(counters_pass.load_registry().spans)
        if name != "ros2.ec.drain")
    lint_source(emitting_all_but_one, passes=["counter"])
    stale = [f for f in counters_pass.finalize([])
             if "SPANS" in f.msg]
    assert [("ros2.ec.drain" in f.msg) for f in stale] == [True]
    lint_source("tracing.span('ros2.ec.drain')", passes=["counter"])
    assert not [f for f in counters_pass.finalize([]) if "SPANS" in f.msg]


def test_exceptions_flags_swallow_and_accepts_reraise_or_typed():
    bad = _lint("""
        def commit(self):
            try:
                self.write()
            except Exception:
                pass
    """, passes=["broad-except"])
    assert _rules(bad) == ["broad-except"]
    clean = _lint("""
        def commit(self):
            try:
                self.write()
            except (StorageError, OSError):
                self.failed += 1
            try:
                self.write()
            except Exception:
                self.cleanup()
                raise
    """, passes=["broad-except"])
    assert clean == []


def test_threads_flags_anonymous_thread_and_pool():
    bad = _lint("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()
            self._pool = ThreadPoolExecutor(max_workers=4)
    """, passes=["thread"])
    assert _rules(bad) == ["thread", "thread"]
    clean = _lint("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def start(self):
            threading.Thread(target=self._loop, name="media-scrub",
                             daemon=True).start()
            self._pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="replica-commit")
    """, passes=["thread"])
    assert clean == []


def test_nondeterminism_flags_unseeded_rng_and_wall_clock():
    bad = _lint("""
        import random
        import time

        def jitter(self):
            self.t0 = time.time()
            return random.random() * self.cap

        def make_rng(self):
            return random.Random()
    """, passes=["nondeterminism"])
    assert _rules(bad) == ["nondeterminism"] * 3
    clean = _lint("""
        import random
        import time

        def jitter(self, seed):
            self.t0 = time.monotonic()
            return random.Random(seed).random() * self.cap
    """, passes=["nondeterminism"])
    # Random(seed).random() is a draw from a SEEDED instance: the
    # `random.<fn>` rule matches only the module-global form
    assert clean == []


# ---------------------------------------------------------------------------
# suppressions: honored, but audited


def test_suppression_with_reason_silences_the_finding():
    clean = _lint("""
        import time

        def pace(self):
            # lint: allow(timeout-literal): fixed cadence, not a deadline
            time.sleep(0.5)
    """, passes=["timeout-literal"], audit_suppressions=True)
    assert clean == []


def test_suppression_without_reason_is_itself_a_finding():
    bad = _lint("""
        import time

        def pace(self):
            time.sleep(0.5)  # lint: allow(timeout-literal)
    """, passes=["timeout-literal"], audit_suppressions=True)
    assert "suppression-empty" in _rules(bad)


def test_unused_suppression_is_flagged():
    bad = _lint("""
        def quiet(self):
            # lint: allow(timeout-literal): stale comment
            return 1
    """, passes=["timeout-literal"], audit_suppressions=True)
    assert _rules(bad) == ["suppression-unused"]


# ---------------------------------------------------------------------------
# lock-order witness


def _locks(graph, *sites):
    return [lockgraph._WitnessLock(threading.Lock(), s, graph)
            for s in sites]


def test_lockgraph_flags_abba_inversion_without_a_deadlock():
    g = lockgraph.LockGraph()
    a, b = _locks(g, "client.py:10", "client.py:20")
    with a:
        with b:
            pass
    with b:                               # opposite order, sequentially:
        with a:                           # never deadlocks, still wrong
            pass
    assert g.cycles() == [["client.py:10", "client.py:20"]]
    report = g.report()
    assert "client.py:10" in report and "client.py:20" in report


def test_lockgraph_consistent_order_is_clean():
    g = lockgraph.LockGraph()
    a, b, c = _locks(g, "a.py:1", "b.py:1", "c.py:1")
    for _ in range(3):
        with a:
            with b:
                with c:
                    pass
    assert g.cycles() == []
    assert g.edges["a.py:1"] == {"b.py:1", "c.py:1"}


def test_lockgraph_same_site_nesting_warns_not_fails():
    g = lockgraph.LockGraph()
    s1, s2 = _locks(g, "ring.py:5", "ring.py:5")   # two instances, 1 site
    with s1:
        with s2:
            pass
    assert g.cycles() == []
    assert g.self_edges == {"ring.py:5"}


def test_lockgraph_rlock_reentry_adds_no_edges():
    g = lockgraph.LockGraph()
    r = lockgraph._WitnessLock(threading.RLock(), "r.py:1", g)
    with r:
        with r:
            pass
    assert g.edges == {}


def test_lockgraph_condition_wait_releases_the_held_set():
    g = lockgraph.LockGraph()
    guard, inner = _locks(g, "outer.py:1", "cv.py:1")
    cv = threading.Condition(inner)
    done = threading.Event()

    def poker():
        with cv:
            cv.notify_all()
        done.set()

    t = threading.Thread(target=poker, name="lockgraph-test-poker")
    with guard:
        with cv:
            t.start()
            cv.wait(timeout=5.0)
    t.join(timeout=5.0)
    assert done.is_set()
    # held order guard -> cv recorded; the poker thread acquired cv
    # while the waiter had RELEASED it — no cv -> guard edge, no cycle
    assert g.edges.get("outer.py:1") == {"cv.py:1"}
    assert g.cycles() == []


def test_lockgraph_factory_wraps_only_repo_allocations(tmp_path):
    if lockgraph.active() is not None:
        pytest.skip("session-wide --lockgraph witness already installed")
    mod = tmp_path / "fake_mod.py"
    mod.write_text(textwrap.dedent("""
        import threading
        A = threading.Lock()
        B = threading.Lock()

        def ab():
            with A:
                with B:
                    pass

        def ba():
            with B:
                with A:
                    pass
    """))
    g = lockgraph.install([str(tmp_path)], label_root=str(tmp_path))
    try:
        ns = {"__file__": str(mod)}
        exec(compile(mod.read_text(), str(mod), "exec"), ns)
        ns["ab"]()
        ns["ba"]()
        # a lock allocated HERE (tests are outside the witnessed prefix)
        # passes through unwrapped
        assert isinstance(threading.Lock(), type(threading.RLock())) \
            or not isinstance(threading.Lock(), lockgraph._WitnessLock)
        assert len(g.cycles()) == 1
        assert sorted(g.cycles()[0]) == ["fake_mod.py:3", "fake_mod.py:4"]
    finally:
        lockgraph.uninstall()


# ---------------------------------------------------------------------------
# leak witness helpers


def test_leakwitness_catches_a_grant_that_outlives_the_client():
    from repro.core.client import ROS2Client
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    mr = c.register_region(64)
    rk = c.client_registry.grant(mr)
    c.close()                  # sweeps the registration…
    problems = leakwitness.client_leaks(c, timeout=0.2)
    assert any("rkey grants leaked" in p for p in problems), problems
    c.client_registry.retire(rk.token)
    assert leakwitness.client_leaks(c, timeout=0.2) == []


def test_client_close_retires_persistent_registrations():
    from repro.core.client import ROS2Client
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = bytes(range(256)) * 16
    c.pwrite(fd, data, 0)
    sink = c.register_region(len(data))
    c.pread_into(fd, len(data), 0, sink, 0)
    assert bytes(sink.buf) == data
    c.close()
    assert leakwitness.client_leaks(c, timeout=0.2) == []
    assert c.client_registry.regions() == []


def test_leakwitness_thread_accounting_sees_repo_threads():
    evt = threading.Event()
    t = threading.Thread(target=evt.wait, name="media-scrub-fake",
                         daemon=True)
    t.start()
    try:
        leaks = leakwitness.thread_leaks(baseline=set(), timeout=0.2)
        assert any("media-scrub-fake" in p for p in leaks)
        # pre-existing threads in the baseline are not leaks
        assert leakwitness.thread_leaks(
            baseline={x.ident for x in threading.enumerate()},
            timeout=0.2) == []
    finally:
        evt.set()
        t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# the repo itself: the merge gate


def test_scoped_repo_lint_is_clean():
    counters_pass._seen_paths.clear()     # hermetic finalize sweep
    counters_pass._seen_spans.clear()
    findings = lint_paths(scoped_files(repo_root()))
    assert findings == [], \
        "repo lint regressions:\n" + "\n".join(f.render()
                                               for f in findings)


def test_counter_registry_matches_live_stats_dataclasses():
    from repro.core import counters_registry
    counters_registry.validate_registry()


def test_counters_verify_rejects_undeclared_keys():
    from repro.core import counters_registry
    with pytest.raises(counters_registry.UndeclaredCounterError):
        counters_registry.verify({"transport": {"not_a_counter": 1}})
    with pytest.raises(counters_registry.UndeclaredCounterError):
        counters_registry.verify({"no_such_section": {}})
