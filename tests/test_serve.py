"""Serving-tier core tests (ISSUE 8): deterministic, seeded, no sleeps.

The scheduler is jax-free by design — model execution hides behind a
two-method runner — so these tests drive ``step()`` on the calling
thread with a scripted fake runner and an injected counter clock.  The
paged arena IS real (its buffers are plain device_put zeros), so the
liveness tests exercise the actual ``Engine.pending_reads`` /
``flush_if_referencing`` path under op bulking.
"""
import itertools

import jax
import numpy as np
import pytest

from mxnet_tpu import engine as engine_mod
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.engine import Engine
from mxnet_tpu.serve import (PagedKVArena, Request, Scheduler,
                             ServeQueueFull)
from mxnet_tpu.serve.model import KVGeometry
from mxnet_tpu.telemetry import memdump


def tiny_geometry(**over):
    kw = dict(num_layers=1, num_heads=2, num_kv_heads=1, head_dim=4,
              units=8, hidden_size=16, vocab_size=32, page_size=4,
              num_pages=9, max_pages_per_seq=4, max_batch=2,
              prefill_buckets=(4, 8))
    kw.update(over)
    return KVGeometry(**kw)


class FakeRunner:
    """Scripted runner: records every call, returns zero logits (token
    choice is the sampler's job, injected per test)."""

    def __init__(self, geometry):
        self.g = geometry
        self.prefills = []
        self.decodes = []

    def prefill(self, bucket, tokens, length, block_row):
        self.prefills.append((bucket, [int(t) for t in tokens],
                              int(length), np.array(block_row)))
        return np.zeros(self.g.vocab_size, dtype=np.float32)

    def decode(self, tokens, positions, block_tables):
        self.decodes.append((np.array(tokens), np.array(positions),
                             np.array(block_tables)))
        return np.zeros((self.g.max_batch, self.g.vocab_size),
                        dtype=np.float32)


def counter_clock(step=0.01):
    c = itertools.count()
    return lambda: next(c) * step


def make_sched(g=None, queue_depth=8, sampler=None):
    g = g or tiny_geometry()
    arena = PagedKVArena(g)
    runner = FakeRunner(g)
    sched = Scheduler(runner, arena, queue_depth=queue_depth,
                      sampler=sampler, clock=counter_clock())
    return sched, runner, arena


def run_to_completion(sched, max_steps=10_000):
    steps = 0
    while sched.has_work():
        sched.step()
        steps += 1
        assert steps < max_steps, "scheduler failed to drain"
    return steps


# -- admission + backpressure -------------------------------------------

def test_queue_backpressure_raises_serve_queue_full():
    sched, _, _ = make_sched(queue_depth=2)
    sched.submit(Request([1, 2], max_new_tokens=4))
    sched.submit(Request([3], max_new_tokens=4))
    with pytest.raises(ServeQueueFull, match="MXNET_SERVE_QUEUE_DEPTH"):
        sched.submit(Request([4], max_new_tokens=4))
    assert sched.rejected == 1 and sched.queue_len() == 2


def test_overlong_prompt_rejected_at_submit():
    sched, runner, _ = make_sched()
    req = sched.submit(Request(list(range(9)), max_new_tokens=2))
    assert req.done()
    with pytest.raises(MXNetError, match="prefill bucket"):
        req.result(timeout=0)
    assert not runner.prefills  # never reached the model


def test_over_context_budget_rejected_at_submit():
    # max_context = 4 pages x 4 tokens = 16; prompt 8 + budget 12 > 16
    sched, _, _ = make_sched()
    req = sched.submit(Request(list(range(8)), max_new_tokens=12))
    assert req.done()
    with pytest.raises(MXNetError, match="max context"):
        req.result(timeout=0)


def test_admission_waits_for_pages_not_slots():
    # one request holds every free page; the queue head must wait even
    # though a decode slot is free, and admit as soon as pages return
    g = tiny_geometry(num_pages=5, max_pages_per_seq=4)  # 4 free pages
    sched, _, arena = make_sched(g)
    big = sched.submit(Request([1, 2, 3, 4], max_new_tokens=12))  # 4 pages
    small = sched.submit(Request([5], max_new_tokens=3))          # 1 page
    sched.step()  # admits big only: arena is out of pages
    assert sched.active_slots() == 1 and sched.queue_len() == 1
    assert arena.free_pages == 0
    run_to_completion(sched)
    assert big.result(timeout=0) is not None
    assert small.result(timeout=0) is not None
    assert arena.free_pages == 4  # every page returned


# -- bucket selection ----------------------------------------------------

def test_prefill_uses_smallest_covering_bucket():
    sched, runner, _ = make_sched()
    sched.submit(Request([1, 2, 3], max_new_tokens=1))     # 3 -> bucket 4
    sched.submit(Request([1] * 5, max_new_tokens=1))       # 5 -> bucket 8
    run_to_completion(sched)
    assert [p[0] for p in runner.prefills] == [4, 8]
    assert sched.pick_bucket(4) == 4 and sched.pick_bucket(8) == 8
    assert sched.pick_bucket(9) is None


# -- EOS + slot recycling ------------------------------------------------

def test_eos_frees_slot_and_next_request_reuses_it():
    g = tiny_geometry(max_batch=1)
    # scripted sampler: first request emits EOS (7) on its 2nd token
    script = {0: iter([5, 7]), 1: iter([6, 6, 6])}

    def sampler(logits, req):
        return next(script[req.rid % 2])

    sched, _, arena = make_sched(g, sampler=sampler)
    a = Request([1, 2], max_new_tokens=8, eos_id=7)
    b = Request([3, 4], max_new_tokens=3)
    a.rid, b.rid = 0, 1  # pin ids for the script
    sched.submit(a)
    sched.submit(b)
    sched.step()  # admit a (sole slot), prefill, decode once
    run_to_completion(sched)
    assert a.result(timeout=0) == [5, 7], "EOS must end the sequence"
    assert b.result(timeout=0) == [6, 6, 6], "recycled slot serves b"
    assert sched.active_slots() == 0
    assert arena.free_pages == arena.total_pages


def test_eos_in_prefill_token_completes_without_decode():
    sched, runner, _ = make_sched(sampler=lambda lg, rq: 9)
    req = sched.submit(Request([1], max_new_tokens=8, eos_id=9))
    sched.step()
    assert req.done() and req.result(timeout=0) == [9]
    assert not runner.decodes  # finished straight out of prefill


# -- decode batching -----------------------------------------------------

def test_inactive_slots_ride_null_page():
    # one active slot out of two: the decode call's inactive lane must
    # carry position 0 and an all-null-page block row
    sched, runner, _ = make_sched(sampler=lambda lg, rq: 3)
    sched.submit(Request([1, 2], max_new_tokens=2))
    run_to_completion(sched)
    assert runner.decodes, "budget 2 needs a decode after prefill"
    tokens, positions, tables = runner.decodes[0]
    active = [i for i in range(2) if positions[i] != 0 or tokens[i] != 0]
    assert len(active) == 1
    inactive = 1 - active[0]
    assert np.all(tables[inactive] == 0), "inactive row must be null page"


def test_two_requests_share_one_decode_batch():
    sched, runner, _ = make_sched(sampler=lambda lg, rq: 3)
    a = sched.submit(Request([1, 2], max_new_tokens=3))
    b = sched.submit(Request([3], max_new_tokens=3))
    run_to_completion(sched)
    assert a.result(timeout=0) == [3, 3, 3]
    assert b.result(timeout=0) == [3, 3, 3]
    # token 0 comes from prefill; the remaining 2 each ride batched steps
    assert sched.decode_steps == 2, "both sequences must share each step"


def test_runner_failure_poisons_slot_and_frees_pages():
    class Boom(FakeRunner):
        def decode(self, *a):
            raise RuntimeError("device fell over")

    g = tiny_geometry()
    arena = PagedKVArena(g)
    sched = Scheduler(Boom(g), arena, queue_depth=4,
                      sampler=lambda lg, rq: 1, clock=counter_clock())
    req = sched.submit(Request([1], max_new_tokens=4))
    sched.step()
    assert req.done()
    with pytest.raises(RuntimeError, match="fell over"):
        req.result(timeout=0)
    assert arena.free_pages == arena.total_pages
    assert sched.active_slots() == 0


# -- deterministic seeded drain -----------------------------------------

def test_seeded_mixed_workload_drains_deterministically():
    from mxnet_tpu.serve import poisson_workload

    def run_once():
        g = tiny_geometry(num_pages=17, max_batch=4)
        sched, runner, arena = make_sched(g, queue_depth=64,
                                          sampler=lambda lg, rq: 2)
        wl = poisson_workload(16, rate_rps=1e9, prompt_range=(1, 8),
                              max_new_range=(1, 8),
                              vocab_size=g.vocab_size, seed=11)
        for _, req in wl:
            sched.submit(req)
        run_to_completion(sched)
        assert arena.free_pages == arena.total_pages
        assert sched.completed == 16
        return ([tuple(req.tokens) for _, req in wl],
                sched.decode_steps, sched.prefills)

    assert run_once() == run_once(), "same seed must replay identically"


def test_ttft_and_percentiles_use_injected_clock():
    sched, _, _ = make_sched(sampler=lambda lg, rq: 1)
    req = sched.submit(Request([1, 2], max_new_tokens=2))
    run_to_completion(sched)
    assert req.ttft is not None and req.ttft > 0
    assert sched.percentile("ttft", 0.5) > 0
    assert sched.percentile("tpot", 0.5) > 0
    st = sched.stats()
    assert st["completed"] == 1 and st["tokens_generated"] == 2
    assert st["ttft_p50_s"] == sched.percentile("ttft", 0.5)


# -- per-request tracing (ISSUE 9) ---------------------------------------

def test_request_trace_records_lifecycle_and_breakdown():
    sched, _, _ = make_sched(sampler=lambda lg, rq: 1)
    req = sched.submit(Request([1, 2, 3], max_new_tokens=3))
    run_to_completion(sched)
    tr = sched.trace(req.trace_id)
    assert tr is not None and tr["rid"] == req.rid
    assert tr["status"] == "completed"
    assert tr["prompt_len"] == 3 and tr["tokens"] == req.tokens
    names = [e["event"] for e in tr["events"]]
    assert names[0] == "submit"
    assert names.index("admit") < names.index("prefill")
    assert names[-1] == "finish"
    # the injected counter clock makes every slice exact and positive
    bd = tr["breakdown"]
    assert bd["queue_wait_s"] == req.admit_t - req.submit_t > 0
    assert bd["prefill_s"] == req.first_token_t - req.admit_t > 0
    assert bd["first_decode_s"] == req.first_decode_t - req.first_token_t
    assert bd["ttft_s"] == req.ttft
    # clock ticks are in the event stream too (monotone non-decreasing)
    ts = [e["t"] for e in tr["events"]]
    assert ts == sorted(ts)


def test_trace_ids_are_unique_and_unknown_id_returns_none():
    sched, _, _ = make_sched()
    a = Request([1], max_new_tokens=1)
    b = Request([2], max_new_tokens=1)
    assert a.trace_id != b.trace_id
    assert sched.trace("nope") is None


def test_rejected_request_leaves_a_trace():
    sched, _, _ = make_sched(queue_depth=0)
    req = Request([1], max_new_tokens=1)
    with pytest.raises(ServeQueueFull):
        sched.submit(req)
    tr = sched.trace(req.trace_id)
    assert tr["status"] == "rejected"
    assert tr["events"][-1]["reason"] == "queue_full"


def test_trace_store_evicts_fifo_at_cap(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_TRACE_CAP", "4")
    sched, _, _ = make_sched(queue_depth=64, sampler=lambda lg, rq: 1)
    reqs = [sched.submit(Request([1], max_new_tokens=1))
            for _ in range(6)]
    run_to_completion(sched)
    kept = [r for r in reqs if sched.trace(r.trace_id) is not None]
    assert len(kept) == 4
    assert kept == reqs[2:]  # oldest two evicted


def test_serve_flight_events_carry_trace_id():
    from mxnet_tpu.telemetry import flight

    flight.reset()
    sched, _, _ = make_sched(sampler=lambda lg, rq: 1)
    req = sched.submit(Request([1, 2], max_new_tokens=2))
    run_to_completion(sched)
    evs = flight.events(kind="serve")
    mine = [e for e in evs if e.get("tid") == req.trace_id]
    kinds = [e["kind"] for e in mine]
    for k in ("serve.submit", "serve.admit", "serve.prefill",
              "serve.first_decode", "serve.finish"):
        assert k in kinds, kinds
    # decode steps are recorded per BATCH, not per request
    assert any(e["kind"] == "serve.decode" for e in evs)


def test_queue_wait_and_first_decode_histograms_populate():
    from mxnet_tpu import telemetry

    sched, _, _ = make_sched(sampler=lambda lg, rq: 1)
    sched.submit(Request([1, 2], max_new_tokens=2))
    run_to_completion(sched)
    snap = telemetry.snapshot()
    for fam in ("mxnet_serve_queue_wait_seconds",
                "mxnet_serve_first_decode_seconds"):
        (series,) = snap[fam]["series"]
        assert series["count"] >= 1, fam


# -- arena ---------------------------------------------------------------

def test_arena_never_hands_out_null_page():
    arena = PagedKVArena(tiny_geometry())
    pages = arena.alloc(arena.total_pages // 2, owner="a")
    pages += arena.alloc(arena.total_pages - len(pages), owner="b")
    assert 0 not in pages and len(set(pages)) == len(pages)
    assert arena.alloc(1, owner="c") is None  # full, not an exception


def test_arena_free_guards_double_free_and_owner():
    arena = PagedKVArena(tiny_geometry())
    pages = arena.alloc(2, owner="a")
    arena.free(pages, owner="a")
    with pytest.raises(MXNetError, match="not allocated"):
        arena.free(pages, owner="a")
    p2 = arena.alloc(1, owner="b")
    with pytest.raises(MXNetError, match="owned by"):
        arena.free(p2, owner="a")


def test_arena_rejects_over_max_pages_per_seq():
    arena = PagedKVArena(tiny_geometry())
    with pytest.raises(MXNetError, match="max_pages_per_seq"):
        arena.alloc(5, owner="a")


def test_block_row_pads_with_null_page():
    arena = PagedKVArena(tiny_geometry())
    pages = arena.alloc(2, owner="a")
    row = arena.block_row(pages)
    assert row.shape == (4,) and row.dtype == np.int32
    assert list(row[:2]) == pages and list(row[2:]) == [0, 0]


def test_arena_alloc_drains_pending_bulk_readers():
    """The never-reuse-a-live-page claim: a bulk segment holding the
    arena buffer as a deferred ext input must flush before pages are
    handed to a new owner — the deferred op reads the pre-reuse
    snapshot, not whatever the next executable scribbles."""
    eng = Engine.get()
    eng.flush_bulk("test_setup")
    arena = PagedKVArena(tiny_geometry())
    # fill the arena so the next alloc can only be served by recycling
    first = arena.alloc(4, owner="a")
    arena.alloc(4, owner="b")
    arena.free(first, owner="a")
    flushes0 = arena.liveness_flushes
    with engine_mod.bulk(64):
        # deferred imperative read of the K arena (an eviction scorer,
        # a debug checksum, ...) — captured as an ext input, not run
        bufs = jax.tree_util.tree_leaves(arena.buffers())
        probe = nd.NDArray(bufs[0]).sum()
        assert eng.pending_reads(bufs) != ()
        reused = arena.alloc(4, owner="c")  # the reuse moment
        assert eng.pending_reads(bufs) == ()
        assert set(reused) == set(first), "free list must recycle pages"
    assert arena.liveness_flushes == flushes0 + 1
    assert float(probe.asnumpy()) == 0.0  # read the pre-reuse snapshot


def test_arena_alloc_skips_flush_when_nothing_pends():
    eng = Engine.get()
    eng.flush_bulk("test_setup")
    arena = PagedKVArena(tiny_geometry())
    arena.alloc(1, owner="a")
    assert arena.liveness_flushes == 0


def test_arena_stress_never_reuses_live_page():
    """Seeded alloc/free churn with deferred readers injected at random
    points: every deferred sum must observe the arena value at its call
    time (zeros — nothing writes), and page accounting must balance."""
    eng = Engine.get()
    eng.flush_bulk("test_setup")
    g = tiny_geometry(num_pages=9)
    arena = PagedKVArena(g)
    rng = np.random.default_rng(3)
    held = {}
    probes = []
    with engine_mod.bulk(64):
        for i in range(200):
            roll = rng.integers(0, 3)
            if roll == 0 and held:
                key = list(held)[int(rng.integers(0, len(held)))]
                arena.free(held.pop(key), owner=key)
            elif roll == 1:
                probes.append(nd.NDArray(arena.buffers()[0][0][0]).sum())
            else:
                n = int(rng.integers(1, g.max_pages_per_seq + 1))
                pages = arena.alloc(n, owner=i)
                if pages is not None:
                    held[i] = pages
    for key in list(held):
        arena.free(held.pop(key), owner=key)
    assert arena.free_pages == arena.total_pages
    for p in probes:
        assert float(p.asnumpy()) == 0.0


# -- n-gram proposer (ISSUE 13) ------------------------------------------

def test_propose_ngram_replays_longest_match():
    from mxnet_tpu.serve import propose_ngram

    # 2-gram [1, 2] matched at the start; continuation replayed
    assert propose_ngram([1, 2, 3, 1, 2], 3) == [3, 1, 2]


def test_propose_ngram_prefers_most_recent_match():
    from mxnet_tpu.serve import propose_ngram

    # [1, 2] occurs twice; the recent occurrence continues with 9, not 5
    assert propose_ngram([7, 1, 2, 5, 1, 2, 9, 1, 2], 1) == [9]


def test_propose_ngram_pads_match_near_the_end():
    from mxnet_tpu.serve import propose_ngram

    # 1-gram [4] matches at index 0, continuation [9, 4] pads to k=3
    assert propose_ngram([4, 9, 4], 3) == [9, 4, 4]


def test_propose_ngram_fallback_repeats_last_token():
    from mxnet_tpu.serve import propose_ngram

    assert propose_ngram([1, 2, 3], 2) == [3, 3]
    assert propose_ngram([5], 4) == [5, 5, 5, 5]


def test_propose_ngram_validates_inputs():
    from mxnet_tpu.serve import propose_ngram

    with pytest.raises(MXNetError, match="k > 0"):
        propose_ngram([1, 2], 0)
    with pytest.raises(MXNetError, match="non-empty"):
        propose_ngram([], 2)


def test_ngram_proposer_matches_scan_proposer():
    # the incremental index the scheduler uses must reproduce the scan
    # version exactly — drafts AND match length — under incremental
    # appends, across random repetitive streams
    from mxnet_tpu.serve import NgramProposer, propose_ngram

    rng = np.random.default_rng(13)
    for _ in range(20):
        hist = [int(t) for t in rng.integers(0, 6, size=40)]
        inc = NgramProposer(hist[:3])
        for i in range(3, len(hist)):
            inc.append(hist[i])
            got = inc.propose(4)
            want = propose_ngram(hist[:i + 1], 4, with_match=True)
            assert got == tuple(want) or list(got) == list(want), \
                (hist[:i + 1], got, want)


def test_ngram_proposer_validates_inputs():
    from mxnet_tpu.serve import NgramProposer

    with pytest.raises(MXNetError, match="k > 0"):
        NgramProposer([1, 2]).propose(0)
    with pytest.raises(MXNetError, match="non-empty"):
        NgramProposer([]).propose(2)


# -- speculative scheduling (ISSUE 13) ------------------------------------

class ScriptedSpecRunner:
    """Position-indexed ground truth: the model's output after the token
    at stream position p is ``seq[p + 1]`` (one-hot logits), regardless
    of how positions are grouped into prefill/decode/verify calls —
    exactly the property the compiled verify graph guarantees."""

    def __init__(self, geometry, seq):
        self.g = geometry
        self.seq = seq
        self.prefills = []
        self.decodes = []
        self.verifies = []

    def _onehot(self, tok):
        v = np.zeros(self.g.vocab_size, np.float32)
        v[int(tok)] = 1.0
        return v

    def prefill(self, bucket, tokens, length, block_row):
        self.prefills.append(int(length))
        return self._onehot(self.seq[int(length)])

    def decode(self, tokens, positions, block_tables):
        self.decodes.append(np.array(positions))
        out = np.zeros((self.g.max_batch, self.g.vocab_size), np.float32)
        for i, p in enumerate(positions):
            out[i] = self._onehot(self.seq[int(p) + 1])
        return out

    def verify(self, tokens, positions, block_tables):
        self.verifies.append((np.array(tokens), np.array(positions)))
        k1 = tokens.shape[1]
        out = np.zeros((self.g.max_batch, k1, self.g.vocab_size),
                       np.float32)
        for i in range(tokens.shape[0]):
            for j in range(k1):
                out[i, j] = self._onehot(self.seq[int(positions[i]) + j + 1])
        return out


class _CostClock:
    """Clock the runner advances by a scripted amount per call, so a
    test can make verify arbitrarily more expensive than decode."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class CostedSpecRunner(ScriptedSpecRunner):
    def __init__(self, geometry, seq, clk, decode_cost=1.0,
                 verify_cost=1.0):
        super().__init__(geometry, seq)
        self.clk = clk
        self.decode_cost = decode_cost
        self.verify_cost = verify_cost

    def decode(self, *a):
        self.clk.t += self.decode_cost
        return super().decode(*a)

    def verify(self, *a):
        self.clk.t += self.verify_cost
        return super().verify(*a)


def test_spec_cost_gate_prefers_decode_when_verify_is_expensive():
    # cost-aware hybrid policy: identical workload under two cost
    # regimes.  When a verify call costs more than its acceptance
    # repays, the scheduler must settle back to plain decode (modulo
    # cold-start and re-probe verifies) — with output unchanged.
    seq = list(range(10, 20)) + [5, 6, 7] * 30
    outs, calls = {}, {}
    for vcost in (1.0, 10.0):
        g = tiny_geometry(spec_k=4, num_pages=32, max_pages_per_seq=14)
        arena = PagedKVArena(g)
        clk = _CostClock()
        runner = CostedSpecRunner(g, seq, clk, verify_cost=vcost)
        sched = Scheduler(runner, arena, queue_depth=8, clock=clk)
        req = sched.submit(Request(seq[:4], max_new_tokens=40))
        run_to_completion(sched)
        outs[vcost] = req.result(timeout=0)
        calls[vcost] = (len(runner.decodes), len(runner.verifies))
    assert outs[1.0] == outs[10.0] == seq[4:44]
    # verify at decode cost: speculation carries the stream
    assert calls[1.0][1] > calls[10.0][1]
    # 10x verify: the gate learns the premium never pays here
    assert calls[10.0][0] > calls[10.0][1]


def test_spec_dormancy_stops_proposing_and_still_reprobes(monkeypatch):
    # ISSUE 14 satellite: runtime spec_k (2) below the compiled width
    # (4) plus a 10x verify premium that never pays — after
    # _SPEC_DORMANT_AFTER losing re-probes the scheduler must stop
    # running the proposers on ordinary steps (dormant), while the
    # probe cadence keeps firing real verifies so a workload shift
    # could still wake the path.  Output stays exactly the plain-decode
    # stream.
    from mxnet_tpu.serve import scheduler as sched_mod
    from mxnet_tpu.serve import spec as spec_mod

    monkeypatch.setattr(sched_mod, "_SPEC_PROBE_EVERY", 4)
    seq = list(range(10, 20)) + [5, 6, 7] * 40

    class SpyRunner(CostedSpecRunner):
        sched = None

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.verify_dormant = []

        def verify(self, *a):
            self.verify_dormant.append(self.sched._spec_dormant)
            return super().verify(*a)

    outs = {}
    for spec_k in (0, 2):
        g = tiny_geometry(spec_k=4, num_pages=64, max_pages_per_seq=30)
        arena = PagedKVArena(g)
        clk = _CostClock()
        runner = SpyRunner(g, seq, clk, verify_cost=10.0)
        sched = Scheduler(runner, arena, queue_depth=8, spec_k=spec_k,
                          clock=clk)
        runner.sched = sched
        proposed_dormant = []
        orig_propose = spec_mod.NgramProposer.propose

        def propose(self, k, _s=sched, _rec=proposed_dormant,
                    _o=orig_propose):
            _rec.append(_s._spec_dormant)
            return _o(self, k)

        monkeypatch.setattr(spec_mod.NgramProposer, "propose", propose)
        req = sched.submit(Request(seq[:4], max_new_tokens=100))
        run_to_completion(sched)
        outs[spec_k] = req.result(timeout=0)
        if spec_k == 0:
            continue
        assert sched._spec_dormant, \
            "losing verify path must park the proposers"
        # dormant steps skip the proposers entirely: strictly fewer
        # propose calls than scheduler steps (pre-dormancy it is 1:1)
        assert len(proposed_dormant) < sched.decode_steps, \
            (len(proposed_dormant), sched.decode_steps)
        # ...but the cost gate still re-probes with real verify calls
        # after going dormant
        assert any(runner.verify_dormant), \
            "dormancy must not kill the re-probe cadence"
    assert outs[0] == outs[2] == seq[4:104]


def make_spec_sched(seq, geom=None, spec_k=None):
    g = geom or tiny_geometry(spec_k=4)
    arena = PagedKVArena(g)
    runner = ScriptedSpecRunner(g, seq)
    sched = Scheduler(runner, arena, queue_depth=8, spec_k=spec_k,
                      clock=counter_clock())
    return sched, runner, arena


def test_spec_accepts_repeating_sequence_in_blocks():
    # period-3 ground truth: the n-gram proposer locks on after a few
    # tokens and verify accepts multi-token blocks
    seq = [5, 6, 7] * 20
    sched, runner, _ = make_spec_sched(seq)
    req = sched.submit(Request(seq[:4], max_new_tokens=8))
    run_to_completion(sched)
    assert req.result(timeout=0) == seq[4:12]
    assert sched.spec_accepted > 0
    assert runner.decodes == [], "spec_k>0 must use verify, not decode"
    # speculation must beat one-token-per-step: 8 tokens, 1 from
    # prefill, the rest in fewer than 7 verify calls
    assert len(runner.verifies) < 7


def test_spec_output_identical_to_spec_off():
    seq = [3, 1, 4, 1, 5, 9] * 12
    outs = {}
    for spec_k in (0, 2, 4):
        sched, _, _ = make_spec_sched(seq, spec_k=spec_k)
        req = sched.submit(Request(seq[:5], max_new_tokens=7))
        run_to_completion(sched)
        outs[spec_k] = req.result(timeout=0)
    assert outs[0] == outs[2] == outs[4] == seq[5:12]


def test_spec_mid_block_eos_truncates_exactly():
    seq = [5, 6, 7] * 20
    sched, runner, _ = make_spec_sched(seq)
    # eos (=5) falls in the middle of the first accepted verify block
    req = sched.submit(Request(seq[:4], max_new_tokens=8, eos_id=5))
    run_to_completion(sched)
    assert req.result(timeout=0) == [6, 7, 5]
    assert len(runner.verifies) == 1, \
        "EOS inside the first block must stop the lane there"
    # and the truncation point matches plain decode exactly
    sched0, _, _ = make_spec_sched(seq, spec_k=0)
    req0 = sched0.submit(Request(seq[:4], max_new_tokens=8, eos_id=5))
    run_to_completion(sched0)
    assert req0.result(timeout=0) == req.result(timeout=0)


def test_spec_mid_block_budget_truncates_exactly():
    seq = [5, 6, 7] * 20
    sched, _, _ = make_spec_sched(seq)
    req = sched.submit(Request(seq[:4], max_new_tokens=4))
    run_to_completion(sched)
    # prefill emits 1, the verify block offers 4 more, budget takes 3
    assert req.result(timeout=0) == seq[4:8]
    sched0, _, _ = make_spec_sched(seq, spec_k=0)
    req0 = sched0.submit(Request(seq[:4], max_new_tokens=4))
    run_to_completion(sched0)
    assert req0.result(timeout=0) == req.result(timeout=0)


def test_spec_full_rejection_falls_back_to_bonus_token():
    # the prompt's repeated bigram [1,2] baits the proposer into a
    # verify block, but the ground truth diverges to fresh tokens —
    # every draft is rejected and the verify still emits exactly the
    # one (bonus) token plain decode would have produced
    seq = [1, 2, 3, 1, 2] + list(range(10, 40))
    sched, runner, _ = make_spec_sched(seq)
    req = sched.submit(Request(seq[:4], max_new_tokens=6))
    run_to_completion(sched)
    assert req.result(timeout=0) == seq[4:10]
    assert sched.spec_accepted == 0
    assert sched.spec_proposed > 0
    assert len(runner.verifies) == 1  # the baited block, fully rejected
    assert len(runner.decodes) == 4  # matchless tail uses plain decode


def test_spec_matchless_history_uses_plain_decode_path():
    # hybrid policy: chain ground truth t -> t+1 never repeats an
    # n-gram, so the scheduler never pays for a verify call at all —
    # and the output still matches spec-off exactly
    seq = list(range(32))
    sched, runner, _ = make_spec_sched(seq)
    req = sched.submit(Request(seq[:4], max_new_tokens=6))
    run_to_completion(sched)
    assert req.result(timeout=0) == seq[4:10]
    assert runner.verifies == []
    assert sched.spec_proposed == 0 and sched.spec_accepted == 0


def test_spec_headroom_tightens_submit_context_check():
    # max_context=16; prompt 6 + budget 8 fits plain but not with the
    # compiled spec_k=4 scatter headroom
    sched, _, _ = make_spec_sched(list(range(32)))
    req = sched.submit(Request(list(range(6)), max_new_tokens=8))
    assert req.done()
    with pytest.raises(MXNetError, match="spec_k headroom"):
        req.result(timeout=0)
    # runtime spec_k=0 on the same bundle geometry restores the old limit
    sched0, _, _ = make_spec_sched(list(range(32)), spec_k=0)
    req0 = sched0.submit(Request(list(range(6)), max_new_tokens=8))
    run_to_completion(sched0)
    assert req0.result(timeout=0) == list(range(6, 14))


def test_runtime_spec_k_validation():
    g = tiny_geometry(spec_k=4)
    arena = PagedKVArena(g)
    with pytest.raises(MXNetError, match="spec_k=5 out of range"):
        Scheduler(ScriptedSpecRunner(g, []), arena, spec_k=5)
    g0 = tiny_geometry()  # compiled without speculation
    with pytest.raises(MXNetError, match="out of range"):
        Scheduler(FakeRunner(g0), PagedKVArena(g0), spec_k=2)


def test_spec_counters_and_stats():
    seq = [5, 6, 7] * 20
    sched, _, _ = make_spec_sched(seq)
    sched.submit(Request(seq[:4], max_new_tokens=8))
    run_to_completion(sched)
    st = sched.stats()
    assert st["spec_k"] == 4 and st["kv_dtype"] == "float32"
    assert st["spec_proposed_tokens"] == sched.spec_proposed > 0
    assert st["spec_accepted_tokens"] == sched.spec_accepted > 0
    assert 0.0 < st["spec_accept_rate"] <= 1.0
    from mxnet_tpu import telemetry

    snap = telemetry.snapshot()
    for fam in ("mxnet_serve_spec_proposed_tokens_total",
                "mxnet_serve_spec_accepted_tokens_total"):
        assert fam in snap, fam
    (series,) = snap["mxnet_serve_spec_accept_length"]["series"]
    assert series["count"] >= 1


# -- int8 arena (ISSUE 13) ------------------------------------------------

def test_int8_arena_stores_quantized_pages_and_scales():
    g = tiny_geometry(kv_dtype="int8")
    arena = PagedKVArena(g)
    assert arena.quantized
    state = arena.buffers()
    assert len(state) == g.num_layers == 1
    for pages, scale in state[0]:                        # K, then V
        assert pages.shape == g.kv_shape() and pages.dtype == np.int8
        assert scale.shape == g.scale_shape() == (9,)
        assert scale.dtype == np.float32
    # a float arena has the same tree with no scale in it
    (k, k_scale), (v, v_scale) = PagedKVArena(tiny_geometry()).buffers()[0]
    assert k_scale is None and v_scale is None
    assert k.shape == v.shape == g.kv_shape()


def test_int8_arena_adopts_the_whole_state():
    g = tiny_geometry(kv_dtype="int8")
    arena = PagedKVArena(g)
    ((k, _), v_side), = arena.buffers()
    ones = jax.device_put(np.ones(g.scale_shape(), np.float32))
    arena.adopt((((k, ones), v_side),))
    assert arena.buffers()[0][0][1] is ones
    # the adopted buffers are attributed like the first ones once asked
    arena.tag()
    assert {r["label"] for r in memdump.topk(1 << 20) if r["origin"] == "kv_page"} \
        >= {"arena.k", "arena.v", "arena.k_scale", "arena.v_scale"}


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_arena_bytes_are_what_the_buffers_hold(kv_dtype):
    g = tiny_geometry(num_layers=3, kv_dtype=kv_dtype)
    held = sum(b.nbytes
               for b in jax.tree_util.tree_leaves(PagedKVArena(g).buffers()))
    assert g.arena_bytes() == held
    # a TPU's (8, 128) tiles: 4 slots a page pad to 8 rows, a 4-wide head
    # to 128 lanes; the scale rows are counted as they are
    size = np.dtype(kv_dtype).itemsize
    scales = 2 * 3 * 9 * 4 * g.quantized
    assert g.arena_bytes() == 2 * 3 * 9 * 1 * 4 * 4 * size + scales
    assert g.arena_bytes(padded=True) == 2 * 3 * 9 * 1 * 8 * 128 * size \
        + scales
    assert "arena=%.3fGB (%.3fGB in a TPU's tiles)" % (
        g.arena_bytes() / 1e9, g.arena_bytes(padded=True) / 1e9) \
        in g.describe()
    # at a 128-wide head and whole tiles nothing pads
    wide = tiny_geometry(head_dim=128, page_size=8, prefill_buckets=(8,))
    assert wide.arena_bytes() == wide.arena_bytes(padded=True)


def test_state_avals_are_a_buffer_a_layer_and_side_in_whole_lanes_on_a_tpu():
    import types

    from mxnet_tpu.serve.model import state_avals

    g = tiny_geometry(num_layers=2, kv_dtype="int8")
    state = state_avals(g)
    assert len(state) == 2
    for (k, k_scale), (v, v_scale) in state:
        for pages, scale in ((k, k_scale), (v, v_scale)):
            assert pages.shape == g.kv_shape() == (9, 1, 4, 4)
            assert scale.shape == g.scale_shape() and scale.dtype == np.float32
    (k, k_scale), _ = state_avals(tiny_geometry())[0]
    assert k_scale is None and k.dtype == np.float32
    # for a TPU the head dim fills whole 128-lane rows: what the chip
    # pads a row-major tile to, and arena_bytes(padded=True) counts it
    tpu = types.SimpleNamespace(platform="tpu")
    for head_dim, lanes in ((4, 128), (128, 128), (192, 256)):
        gt = tiny_geometry(head_dim=head_dim, page_size=8,
                           prefill_buckets=(8,))
        ((k, _), (v, _)), = state_avals(gt, tpu)
        assert k.shape == v.shape == (9, 1, 8, lanes)
        assert gt.arena_bytes(padded=True) == 2 * k.size * k.dtype.itemsize


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("paged_kernel", ["0", "1"])
def test_pages_in_whole_lanes_give_the_same_logits(paged_kernel, kv_dtype):
    """What a TPU's programs do with a head narrower than 128 lanes, run
    here: pages 128 wide, zeros past the head dim, through prefill and
    three decode steps against pages as wide as the head."""
    import types

    from mxnet_tpu.serve import model as M

    g = tiny_geometry(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                      units=32, kv_dtype=kv_dtype, paged_kernel=paged_kernel)
    rng = np.random.default_rng(0)
    weights = jax.tree_util.tree_map(
        lambda a: rng.standard_normal(a.shape).astype(a.dtype) * 0.3,
        M.weight_avals(g))
    prompt = np.array([3, 1, 4, 1, 5, 0, 0, 0], np.int32)
    table = np.array([2, 5, 0, 0], np.int32)

    def run(device):
        state = jax.tree_util.tree_map(
            lambda a: np.zeros(a.shape, a.dtype), M.state_avals(g, device))
        state, logits = jax.jit(M.build_prefill_fn(g, 8))(
            state, weights, prompt, np.int32(5), table)
        out = [np.asarray(logits)]
        decode = jax.jit(M.build_decode_fn(g))
        tables = np.stack([table, np.zeros(4, np.int32)])
        for pos in (5, 6, 7):
            state, logits = decode(state, weights,
                                   np.array([7, 0], np.int32),
                                   np.array([pos, 0], np.int32), tables)
            out.append(np.asarray(logits[0]))
        return state, out

    narrow, want = run(None)
    wide, got = run(types.SimpleNamespace(platform="tpu"))
    assert wide[0][0][0].shape == (9, 2, 4, 128)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    for (k, k_sc), (k_narrow, k_sc_narrow) in zip(wide[1], narrow[1]):
        # (the second layer's keys carry the first's attention: rounding)
        np.testing.assert_allclose(
            np.asarray(k)[..., :8].astype(np.float32),
            np.asarray(k_narrow).astype(np.float32),
            atol=1 if g.quantized else 1e-5)
        assert not np.asarray(k)[..., 8:].any()
        if g.quantized:
            np.testing.assert_allclose(k_sc, k_sc_narrow, rtol=1e-5)


def test_geometry_kv_dtype_and_spec_k_validation():
    with pytest.raises(MXNetError, match="int8"):
        tiny_geometry(kv_dtype="int4")
    with pytest.raises(MXNetError, match="spec_k"):
        tiny_geometry(spec_k=-1)
    with pytest.raises(MXNetError, match="spec_k"):
        tiny_geometry(spec_k=65)


def test_old_schema_geometry_dict_defaults_fp32_no_spec():
    # a pre-PR-13 bundle dict has neither kv_dtype nor spec_k: it must
    # load as an fp32 arena with speculation off (backward compat)
    d = tiny_geometry().to_dict()
    del d["kv_dtype"], d["spec_k"]
    g = KVGeometry.from_dict(d, origin="old-bundle")
    assert g.kv_dtype == "float32" and g.spec_k == 0 and not g.quantized


def test_check_geometry_names_kv_dtype_and_spec_k():
    from mxnet_tpu.serve import check_geometry

    got = tiny_geometry(kv_dtype="int8", spec_k=4)
    with pytest.raises(MXNetError) as ei:
        check_geometry(got, {"kv_dtype": "float32", "spec_k": 0})
    msg = str(ei.value)
    assert "kv_dtype" in msg and "spec_k" in msg
    assert "int8" in msg and "refusing to serve" in msg


# -- request surface -----------------------------------------------------

def test_request_validates_inputs():
    with pytest.raises(MXNetError, match="empty"):
        Request([])
    with pytest.raises(MXNetError, match="positive"):
        Request([1], max_new_tokens=0)


def test_request_result_timeout_message():
    req = Request([1], max_new_tokens=1)
    with pytest.raises(MXNetError, match="in flight"):
        req.result(timeout=0)


# -- lifecycle: deadlines, cancellation, drain, shutdown (ISSUE 15) ------

def _lifecycle_imports():
    from mxnet_tpu.serve import (ServeCancelled, ServeDeadlineExceeded,
                                 ServeDraining, ServeInternalError,
                                 ServeShutdown)
    return (ServeCancelled, ServeDeadlineExceeded, ServeDraining,
            ServeInternalError, ServeShutdown)


def test_deadline_must_be_positive():
    with pytest.raises(MXNetError, match="positive"):
        Request([1], max_new_tokens=1, deadline_s=-2)


def test_deadline_expires_in_queue():
    _, ServeDeadlineExceeded, _, _, _ = _lifecycle_imports()
    g = tiny_geometry(max_batch=1)
    sched, _, arena = make_sched(g)
    hog = sched.submit(Request([1, 2], max_new_tokens=8))
    late = sched.submit(Request([3], max_new_tokens=2, deadline_s=0.02))
    run_to_completion(sched)      # counter clock: queue wait >> 0.02s
    assert hog.error is None
    with pytest.raises(ServeDeadlineExceeded, match="deadline_s"):
        late.result(timeout=0)
    assert late.tokens == []      # never admitted: reaped from the queue
    arena.assert_quiescent()


def test_deadline_expires_mid_decode_and_frees_pages():
    _, ServeDeadlineExceeded, _, _, _ = _lifecycle_imports()
    sched, _, arena = make_sched()
    req = sched.submit(Request([1, 2], max_new_tokens=14, deadline_s=0.2))
    sched.step()                  # admit + prefill: one token exists
    assert sched.active_slots() == 1
    for _ in range(200):          # counter clock marches past deadline_t
        if req.done():
            break
        sched.step()
    with pytest.raises(ServeDeadlineExceeded, match="token"):
        req.result(timeout=0)
    assert 1 <= len(req.tokens) < 14   # partial progress, then the axe
    assert sched.active_slots() == 0   # lane recycled immediately
    arena.assert_quiescent()


def test_default_deadline_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_DEFAULT_DEADLINE", "12.5")
    req = Request([1], max_new_tokens=1)
    assert req.deadline_s == 12.5
    # explicit per-request value wins over the env default
    assert Request([1], max_new_tokens=1, deadline_s=3.0).deadline_s == 3.0
    monkeypatch.setenv("MXNET_SERVE_DEFAULT_DEADLINE", "0")
    assert Request([1], max_new_tokens=1).deadline_s is None


def test_cancel_queued_request():
    ServeCancelled, _, _, _, _ = _lifecycle_imports()
    g = tiny_geometry(max_batch=1)
    sched, runner, arena = make_sched(g)
    hog = sched.submit(Request([1, 2], max_new_tokens=8))
    victim = sched.submit(Request([3], max_new_tokens=2))
    assert sched.cancel(victim.trace_id) is True
    run_to_completion(sched)
    assert hog.error is None
    with pytest.raises(ServeCancelled, match="cancelled"):
        victim.result(timeout=0)
    assert len(runner.prefills) == 1   # the victim never touched the model
    arena.assert_quiescent()


def test_cancel_in_flight_recycles_lane_at_step_boundary():
    ServeCancelled, _, _, _, _ = _lifecycle_imports()
    sched, _, arena = make_sched()
    req = sched.submit(Request([1, 2], max_new_tokens=10))
    sched.step()
    assert sched.active_slots() == 1
    assert req.cancel() is None or True   # API returns None; just call it
    sched.step()                          # reap runs at the boundary
    with pytest.raises(ServeCancelled):
        req.result(timeout=0)
    assert sched.active_slots() == 0
    arena.assert_quiescent()


def test_cancel_unknown_trace_id_returns_false():
    sched, _, _ = make_sched()
    assert sched.cancel("req-nope") is False


def test_cancellation_wins_over_expiry():
    ServeCancelled, _, _, _, _ = _lifecycle_imports()
    sched, _, arena = make_sched()
    req = sched.submit(Request([1, 2], max_new_tokens=4, deadline_s=0.01))
    req.cancel()
    for _ in range(50):
        if req.done():
            break
        sched.step()
    with pytest.raises(ServeCancelled):   # not ServeDeadlineExceeded
        req.result(timeout=0)
    arena.assert_quiescent()


def test_drain_refuses_new_submits_with_retry_after():
    _, _, ServeDraining, _, _ = _lifecycle_imports()
    sched, _, arena = make_sched()
    served = sched.submit(Request([1, 2], max_new_tokens=4))
    sched.drain()
    with pytest.raises(ServeDraining) as ei:
        sched.submit(Request([3], max_new_tokens=2))
    assert ei.value.retry_after_s >= 1
    run_to_completion(sched)              # in-flight work still finishes
    assert served.error is None
    assert sched.stats()["draining"] is True
    arena.assert_quiescent()


def test_server_stop_fails_queued_requests_typed():
    _, _, _, _, ServeShutdown = _lifecycle_imports()
    from mxnet_tpu.serve.server import LlamaServer

    g = tiny_geometry()
    arena = PagedKVArena(g)
    srv = LlamaServer.from_parts(FakeRunner(g), arena, queue_depth=8,
                                 clock=counter_clock())
    req = srv.scheduler.submit(Request([1, 2], max_new_tokens=4))
    srv.stop()                            # never started: queue non-empty
    with pytest.raises(ServeShutdown, match="stopped"):
        req.result(timeout=0)
    arena.assert_quiescent()


def test_retry_after_scales_with_backlog():
    sched, _, _ = make_sched()
    assert sched.retry_after_s() == 1     # empty queue, cold EMA
    # warm the TPOT EMA, then pile a backlog on
    first = sched.submit(Request([1, 2], max_new_tokens=8))
    run_to_completion(sched)
    assert first.error is None
    for i in range(3):
        sched.submit(Request([1 + i], max_new_tokens=12))
    shallow = sched.retry_after_s()
    for i in range(3):
        sched.submit(Request([4 + i], max_new_tokens=12))
    # inside the clamp band, and a deeper queue asks for a longer wait
    assert 0.05 <= shallow < sched.retry_after_s() <= 30


# -- arena quiescence + lifecycle stress ---------------------------------

def test_assert_quiescent_names_the_leak():
    g = tiny_geometry()
    arena = PagedKVArena(g)
    arena.assert_quiescent()              # fresh arena is clean
    pages = arena.alloc(2, owner="req-leaky")
    with pytest.raises(MXNetError, match="req-leaky"):
        arena.assert_quiescent()
    arena.free(pages, owner="req-leaky")
    arena.assert_quiescent()


def test_arena_reset_refuses_live_pages_then_rebuilds():
    g = tiny_geometry()
    arena = PagedKVArena(g)
    pages = arena.alloc(3, owner="req-live")
    with pytest.raises(MXNetError, match="live page"):
        arena.reset()
    arena.free(pages, owner="req-live")
    arena.reset()
    assert arena.free_pages == arena.total_pages
    arena.assert_quiescent()


def test_expire_cancel_stress_no_leaks_no_hangs():
    """200 seeded iterations of mixed deadline/cancel/normal traffic;
    after each drain the arena must be quiescent and every future
    resolved — the slow-death leak check (ISSUE 15 satellite)."""
    import os as _os

    (ServeCancelled, ServeDeadlineExceeded, _, _,
     _) = _lifecycle_imports()
    rng = np.random.default_rng(
        int(_os.environ.get("MXNET_CHAOS_SEED", "1337")))
    sched, _, arena = make_sched()
    for it in range(200):
        reqs = []
        for _ in range(int(rng.integers(1, 5))):
            kind = rng.integers(0, 3)
            deadline = 0.05 * float(rng.integers(1, 30)) \
                if kind == 1 else None
            req = Request([1 + int(rng.integers(0, 8))],
                          max_new_tokens=int(rng.integers(1, 8)),
                          deadline_s=deadline)
            try:
                sched.submit(req)
            except MXNetError:
                continue          # queue-full backpressure: fine
            reqs.append((kind, req))
        for kind, req in reqs:
            if kind == 2 and rng.random() < 0.7:
                sched.cancel(req.trace_id)
        steps = 0
        while sched.has_work():
            sched.step()
            steps += 1
            assert steps < 5000, "stress hung at iteration %d" % it
        for _, req in reqs:
            assert req.done(), "unresolved future at iteration %d" % it
            if req.error is not None:
                assert isinstance(req.error, (ServeCancelled,
                                              ServeDeadlineExceeded))
        arena.assert_quiescent()
