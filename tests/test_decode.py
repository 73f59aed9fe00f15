"""Interactive decode engine: paged KV cache, Pallas decode attention,
continuous token-level batching, quantized matmuls, tp serving
(mxnet_tpu/serving/decode.py + ops additions — ISSUE 15)."""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                      DecodeProgram, PagePool,
                                      decode_retrace_report,
                                      decode_tp_model_bytes,
                                      init_decode_params)
from mxnet_tpu.serving.errors import (DeadlineExceeded, Overloaded,
                                      SwapFailed, TopologyMismatch)

VOCAB, T, L, H, HEADS = 29, 16, 2, 24, 2


@pytest.fixture(scope="module")
def toy():
    """One compiled toy program shared across the module — the decode
    step compiles ONCE, and every test riding this fixture doubles as a
    compile-once assertion (trace_count is checked at the end)."""
    cfg = DecodeConfig(VOCAB, L, H, HEADS, T, page_size=4, max_seqs=3)
    params = init_decode_params(cfg, seed=3)
    prog = DecodeProgram(params, cfg, name="toy")
    prog.ensure_compiled()
    return cfg, params, prog


def _contiguous_table(cfg, n=None):
    n = n or cfg.max_seqs
    pp = cfg.pages_per_seq
    table = np.zeros((cfg.max_seqs, pp), np.int32)
    for s in range(n):
        table[s] = 1 + s * pp + np.arange(pp)
    return table


def _first_logits(prog, toks=None):
    cfg = prog.config
    S = cfg.max_seqs
    kv = prog.fresh_cache()
    toks = (np.arange(S, dtype=np.int32) % cfg.vocab_size
            if toks is None else toks)
    pos = np.zeros(S, np.int32)
    table = _contiguous_table(cfg)
    _nxt, logits, _kv = prog.step(kv, toks, pos, pos + 1,
                                  table[:, 0].copy(),
                                  np.zeros(S, np.int32), table)
    return np.asarray(logits)


def test_page_pool_alloc_free_exhaustion():
    pool = PagePool(6)                  # page 0 = trash, 5 usable
    assert pool.available == 5
    a = pool.alloc(3)
    assert a is not None and 0 not in a
    assert pool.alloc(3) is None        # partial grants never happen
    assert pool.available == 2
    b = pool.alloc(2)
    pool.free(a)
    assert pool.available == 3
    pool.free(b)
    assert pool.available == 5


def test_quantize_weight_and_quant_matmul():
    rs = np.random.RandomState(0)
    w = rs.randn(24, 32).astype(np.float32)
    x = rs.randn(5, 32).astype(np.float32)
    ref = x @ w.T
    for bits, tol in ((8, 0.02), (4, 0.25)):
        qw, sc = pk.quantize_weight(w, bits)
        if bits == 4:
            assert qw.shape == (24, 16) and qw.dtype == np.uint8
        else:
            assert qw.dtype == np.int8
        ya = np.asarray(pk.quant_matmul(x, qw, sc, bits,
                                        use_pallas=False))
        yb = np.asarray(pk.quant_matmul(x, qw, sc, bits, use_pallas=True,
                                        block_n=8, block_k=16))
        # dequant-fused pallas kernel == XLA formulation to roundoff
        assert np.abs(ya - yb).max() < 1e-4
        # quantization error bounded relative to the result scale
        rel = np.abs(ya - ref).max() / np.abs(ref).max()
        assert rel < tol, (bits, rel)


@pytest.mark.parametrize("lens", [
    [5, 12, 0],         # partial page, full, inactive
    [3, 4, 5],          # one before, on and one after a page boundary
    [8, 9, 1],          # the same a page on, and a single token
], ids=["partial_full_inactive", "page_edges", "second_page_edges"])
def test_decode_attention_paged_matches_reference(lens):
    rs = np.random.RandomState(0)
    S, nH, D, page, MP, P = 3, 2, 8, 4, 3, 10
    q = rs.randn(S, nH, D).astype(np.float32)
    kp = rs.randn(P, nH, page, D).astype(np.float32)
    vp = rs.randn(P, nH, page, D).astype(np.float32)
    pt = rs.randint(0, P, (S, MP)).astype(np.int32)
    lens = np.array(lens, np.int32)

    ref = np.zeros((S, nH, D), np.float32)
    for s in range(S):
        tl = int(lens[s])
        if tl == 0:
            continue
        ks = np.concatenate([kp[pt[s, j]] for j in range(MP)],
                            axis=1)[:, :tl]
        vs = np.concatenate([vp[pt[s, j]] for j in range(MP)],
                            axis=1)[:, :tl]
        sc = np.einsum("hd,htd->ht", q[s], ks) / np.sqrt(D)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref[s] = np.einsum("ht,htd->hd", p, vs)

    for use_pallas in (False, True):
        out = np.asarray(pk.decode_attention(q, kp, vp, pt, lens,
                                             use_pallas=use_pallas))
        live = lens > 0
        assert np.abs(out[live] - ref[live]).max() < 1e-5, use_pallas
        assert np.isfinite(out).all()    # inactive slot: garbage but finite


def test_decode_step_matches_training_forward(toy):
    """The weight-sharing golden test: teacher-forced decode through the
    paged cache reproduces the training graph's full-sequence logits at
    every position (same params, training names, via the
    models/transformer.get_decode_step entry point)."""
    from mxnet_tpu.models.transformer import get_decode_step, get_symbol
    cfg, params, _prog = toy
    net = get_symbol(vocab_size=VOCAB, seq_len=T, num_layers=L,
                     hidden=H, heads=HEADS)
    logits_sym = net.get_internals()["head_output"]
    N = cfg.max_seqs
    ex = logits_sym.simple_bind(mx.cpu(), data=(N, T),
                                head_weight=(VOCAB, H),
                                head_bias=(VOCAB,))
    for name, arr in ex.arg_dict.items():
        if name != "data":
            arr[:] = params[name]
    rs = np.random.RandomState(1)
    toks = rs.randint(0, VOCAB, (N, T)).astype(np.float32)
    ex.arg_dict["data"][:] = toks
    ref = ex.forward(is_train=False)[0].asnumpy()      # (N, T, V)

    prog = get_decode_step(params, vocab_size=VOCAB, seq_len=T,
                           num_layers=L, hidden=H, heads=HEADS,
                           page_size=cfg.page_size, max_seqs=N)
    kv = prog.fresh_cache()
    table = _contiguous_table(cfg)
    for t in range(T):
        pos = np.full(N, t, np.int32)
        _nxt, logits, kv = prog.step(
            kv, toks[:, t].astype(np.int32), pos, pos + 1,
            table[np.arange(N), t // cfg.page_size],
            np.full(N, t % cfg.page_size, np.int32), table)
        err = np.abs(np.asarray(logits) - ref[:, t]).max()
        assert err < 1e-4, (t, err)
    assert prog.trace_count == 1


def test_engine_continuous_batching_parity_and_compile_once(toy):
    """Mixed-length requests joining/leaving the batch mid-generation
    produce EXACTLY the tokens serial generation produces, with more
    requests than slots, and the step program never retraces."""
    from mxnet_tpu.telemetry import tracing
    cfg, _params, prog = toy
    traces_before = prog.trace_count
    seconds_before = tracing.compile_summary()["by_name"] \
        .get("decode_step", 0.0)
    assert seconds_before > 0          # the fixture's ONE visible compile
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, VOCAB, n) for n in (3, 7, 2, 5, 4)]
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs = [r.result(timeout=60)[0] for r in reqs]
        st = eng.stats()
    assert st["decode"]["tokens_decoded"] == 5 * 6
    assert st["decode"]["occupancy_mean"] > 0.5
    assert st["decode"]["pages_free"] == st["decode"]["pages_total"]
    # serial reference on the SAME program (no recompile)
    with DecodeEngine(prog) as eng2:
        for p, o in zip(prompts, outs):
            assert eng2.generate(p, max_new_tokens=6).tolist() \
                == o.tolist()
    assert prog.trace_count == traces_before  # zero retraces, any lengths
    # and from the compile/* span family: zero decode_step compile
    # seconds accrued while serving (the warmup compile is the only one)
    assert tracing.compile_summary()["by_name"] \
        .get("decode_step", 0.0) == seconds_before


def test_engine_deadline_and_eviction_no_late_ok(toy):
    cfg, _params, prog = toy
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        # deadline expires MID-generation -> typed DeadlineExceeded,
        # pages freed, never a late OK
        doomed = eng.submit(np.array([1, 2], np.int32),
                            max_new_tokens=13, deadline=0.001)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30)
        # slot + page pressure with priority: three low-prio sequences
        # saturate every slot and the whole pool; a high-prio arrival
        # evicts the cheapest running sequence
        long_reqs = [eng.submit(np.array([1, 2], np.int32),
                                max_new_tokens=12, priority=0)
                     for _ in range(3)]
        import time as _time
        deadline_at = _time.monotonic() + 10.0
        while (eng.stats()["decode"]["active_slots"] < 3
               and _time.monotonic() < deadline_at):
            _time.sleep(0.001)
        assert eng.stats()["decode"]["active_slots"] == 3
        vip = eng.submit(np.array([3] * 2, np.int32), max_new_tokens=13,
                         priority=5, deadline=30.0)
        assert vip.result(timeout=30)[0].size == 13
        evicted = 0
        for r in long_reqs:
            try:
                r.result(timeout=30)
            except (Overloaded, DeadlineExceeded):
                evicted += 1
        st = eng.stats()
    assert evicted >= 1        # page pressure evicted a cheaper sequence
    assert st["decode"]["pages_free"] == st["decode"]["pages_total"]
    # every settled OK was on time (the late-OK invariant)
    assert doomed.done and doomed.latency is not None


def _greedy_alone(prog, prompt, max_new, slot=0):
    """The serial greedy reference: ``DecodeProgram`` stepped alone, one
    sequence in ``slot``, every token through the host (the
    eight-argument call)."""
    c = prog.config
    S = c.max_seqs
    table = np.zeros((S, c.pages_per_seq), np.int32)
    table[slot] = 1 + np.arange(c.pages_per_seq)
    kv = prog.fresh_cache()
    fed, out = [int(t) for t in prompt], []
    for pos in range(len(prompt) + max_new - 1):
        args = [np.zeros(S, np.int32) for _ in range(5)]
        for a, v in zip(args, (fed[pos], pos, pos + 1,
                               table[slot, pos // c.page_size],
                               pos % c.page_size)):
            a[slot] = v
        nxt, _logits, kv = prog.step(kv, *args, table)
        if pos + 1 >= len(prompt):
            out.append(int(np.asarray(nxt)[slot]))
            fed.append(out[-1])
            if out[-1] == c.eos_id:
                break
    return out


def test_engine_eos_ends_a_step_late_and_drops_the_overrun(toy):
    """(a) A sequence that emits ``eos_id`` is found out one step late:
    the step dispatched behind the one that produced it ran for it too.
    That step's token is dropped and not counted, the pages come back,
    and the request that takes them over decodes as if alone."""
    _cfg, params, _prog = toy
    plain = DecodeConfig(VOCAB, L, H, HEADS, T, page_size=4, max_seqs=1)
    rs = np.random.RandomState(0)
    p1, p2 = rs.randint(0, VOCAB, 3), rs.randint(0, VOCAB, 5)
    free_run = _greedy_alone(DecodeProgram(params, plain, name="noeos"),
                             p1, 13)
    eos = free_run[2]
    want1 = free_run[:free_run.index(eos) + 1]
    assert len(want1) < 13
    cfg = DecodeConfig(VOCAB, L, H, HEADS, T, page_size=4, max_seqs=1,
                       eos_id=eos)
    prog = DecodeProgram(params, cfg, name="eos")
    want2 = _greedy_alone(prog, p2, 11)
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        # each needs all four pages of the pool: the second can only run
        # in the pages the first gives back
        assert eng.stats()["decode"]["pages_total"] == 4
        r1 = eng.submit(p1, max_new_tokens=13)
        r2 = eng.submit(p2, max_new_tokens=11)
        out1 = r1.result(timeout=60)[0].tolist()
        out2 = r2.result(timeout=60)[0].tolist()
        # r2 may have ended on eos too: let its overrun step come in
        give_up = time.monotonic() + 10.0
        while eng._flight is not None and time.monotonic() < give_up:
            time.sleep(0.001)
    st = eng.stats()        # closed: the worker has written its last
    assert out1 == want1 and out1[-1] == eos
    assert out2 == want2
    # the overrun steps ran (they are steps) and produced nothing
    overruns = 1 + (out2[-1] == eos and len(out2) < 11)
    assert st["decode"]["tokens_decoded"] == len(out1) + len(out2)
    assert st["decode"]["tokens_prefilled"] == (3 - 1) + (5 - 1)
    assert st["counters"]["steps"] == (3 + len(out1) - 1) \
        + (5 + len(out2) - 1) + overruns
    assert len(r1.token_times) == len(out1)
    assert st["counters"]["completed"] == 2
    assert st["decode"]["pages_free"] == st["decode"]["pages_total"]
    assert prog.trace_count == 1


def test_pipelined_engine_equals_the_serial_reference(toy):
    """(b) Mixed lengths through the loop that keeps one step in flight
    come out token for token as from the program stepped alone, every
    token fed is counted once, and the step is still one executable."""
    cfg, _params, prog = toy
    rs = np.random.RandomState(7)
    work = [(rs.randint(0, VOCAB, n), m)
            for n, m in ((3, 6), (7, 9), (1, 4), (5, 1), (4, 12), (9, 2),
                         (2, 7))]
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        reqs = [eng.submit(p, max_new_tokens=m) for p, m in work]
        outs = [r.result(timeout=60)[0].tolist() for r in reqs]
        st = eng.stats()
    for (p, m), out in zip(work, outs):
        assert out == _greedy_alone(prog, p, m)
    d = st["decode"]
    assert d["tokens_prefilled"] + d["tokens_decoded"] \
        == sum(len(p) + m - 1 for p, m in work)
    assert d["tokens_decoded"] == sum(m for _p, m in work)
    assert d["compiles"] == prog.trace_count == 1
    # a pipeline starts only when nothing is in flight, which takes a new
    # admission into an engine that had drained: once a request at most
    steps = st["counters"]["steps"]
    assert steps - len(work) <= d["steps_overlapped"] < steps
    assert d["pages_free"] == d["pages_total"]


def test_exec_error_with_a_step_in_flight(toy):
    """(c) A dispatch that raises while the step before it is in flight:
    every running request fails typed, the step in flight is dropped (no
    token of it counted, nobody late-OKs), and the next request is
    served from a fresh pool."""
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving.errors import ExecFailed
    cfg, _params, prog = toy
    rs = np.random.RandomState(2)
    # prompts of six: steps 1-4 take prompt tokens only
    p1, p2, p3 = (rs.randint(0, VOCAB, 6) for _ in range(3))
    try:
        with DecodeEngine(prog, default_deadline=60.0,
                          breaker_threshold=100) as eng, \
                chaos.inject("exec_error", at_step=4):
            r1 = eng.submit(p1, max_new_tokens=8)
            r2 = eng.submit(p2, max_new_tokens=8)
            with pytest.raises(ExecFailed):
                r1.result(timeout=60)
            # r2 was running too, unless this thread was held up between
            # the two submits for four whole steps
            try:
                served = [r2.result(timeout=60)[0].tolist()]
                assert served[0] == _greedy_alone(prog, p2, 8)
            except ExecFailed:
                served = []
            r3 = eng.submit(p3, max_new_tokens=5)
            served.append(r3.result(timeout=60)[0].tolist())
            assert served[-1] == _greedy_alone(prog, p3, 5)
        st = eng.stats()    # closed: the worker has written its last
    finally:
        chaos.reset()
    assert not r1.generated and r1.latency is not None
    assert st["counters"]["exec_failures"] == 1
    assert st["counters"]["completed"] == len(served)
    assert st["decode"]["tokens_decoded"] == sum(len(o) for o in served)
    if len(served) == 1:
        # steps 1 and 2 were taken in, step 3 was in flight when step 4's
        # dispatch raised and is gone with its two prompt tokens
        assert st["counters"]["steps"] == 2 + (6 + 5 - 1)
        assert st["decode"]["tokens_prefilled"] <= 2 * 2 + 5
    assert st["decode"]["pages_free"] == st["decode"]["pages_total"]
    assert prog.trace_count == 1


def test_step_wrapper_sees_host_seq_lens_and_its_own_tokens(
        toy, monkeypatch):
    """(d) What a wrapper round ``prog.step`` (the benchmark's) relies
    on: ``seq_lens`` by name at its place, a host array; a page table
    that is the step's own copy; and the engine feeding the next step
    exactly the tokens the wrapper handed back, altered or not."""
    import inspect
    cfg, _params, prog = toy
    inner = prog.step
    names = list(inspect.signature(inner).parameters)
    assert names[:7] == ["kv", "tokens", "positions", "seq_lens", "phys",
                         "off", "page_table"] and names[7] == "prev_tok"
    calls, handed = [], []

    def step(*args, **kwargs):
        assert not kwargs
        calls.append(args)
        out = inner(*args)
        tok = ((np.array(out[0]) + 1) % VOCAB).astype(np.int32)
        handed.append(tok)
        return (tok,) + tuple(out[1:])

    monkeypatch.setattr(prog, "step", step)
    prompt = np.arange(3) % VOCAB
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        out = eng.generate(prompt, max_new_tokens=5).tolist()
        table = eng._table
    assert len(calls) == 3 + 5 - 1
    for k, args in enumerate(calls):
        seq_lens, page_table = args[3], args[6]
        assert type(seq_lens) is np.ndarray and seq_lens[0] == k + 1
        assert type(page_table) is np.ndarray
        assert not np.shares_memory(page_table, table)
        # past the prompt the slot's token is -1 and comes from prev_tok,
        # which is what the wrapper returned one call earlier
        assert args[1][0] == (prompt[k] if k < 3 else -1)
        assert args[7] is (handed[k - 1] if k else None)
    # and the caller got the altered tokens, not the program's
    assert out == [int(h[0]) for h in handed[2:]]


def test_lone_generate_takes_its_steps_and_no_idle_sleep(toy, monkeypatch):
    """(e) One request alone: n + m - 1 steps, all but the first
    dispatched behind another, and when its last step is out the loop
    fetches it at once and does not wait on the queue first."""
    cfg, _params, prog = toy
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        waits = []
        pop_live = eng._queue.pop_live

        def watched(timeout=0):
            if timeout and eng._flight is not None:
                waits.append(timeout)
            return pop_live(timeout=timeout)

        monkeypatch.setattr(eng._queue, "pop_live", watched)
        out = eng.generate(np.arange(4) % VOCAB, max_new_tokens=6)
        st = eng.stats()
        one = eng.generate(np.arange(1) % VOCAB, max_new_tokens=1)
        st1 = eng.stats()
    assert out.tolist() == _greedy_alone(prog, np.arange(4) % VOCAB, 6)
    assert st["counters"]["steps"] == 4 + 6 - 1
    assert st["decode"]["steps_overlapped"] == 4 + 6 - 2
    # a one-token request is one step with nothing to overlap
    assert len(one) == 1
    assert st1["counters"]["steps"] - st["counters"]["steps"] == 1
    assert st1["decode"]["steps_overlapped"] == 4 + 6 - 2
    assert not waits


@pytest.fixture
def spans_seen(monkeypatch):
    """[(name, attrs)] of every ``telemetry.span`` closed while the test
    runs, on whatever thread."""
    from mxnet_tpu import telemetry
    seen = []

    class Recording(telemetry.span):
        def __exit__(self, *exc):
            seen.append((self.name, dict(self.attrs)))
            return super().__exit__(*exc)

    monkeypatch.setattr(telemetry, "span", Recording)
    return seen


def test_engine_says_what_it_knew_of_the_device(toy, spans_seen):
    """(f) The pipeline's own account: a step's span says whether the step
    in flight had ended at its dispatch (``prev_ready``), a fetch names its
    step and says whether it found it ended (``ready``), a retire counts the
    tokens it handed out, a settled request leaves one
    ``serve/request_done``; ``stats()`` sums the same."""
    cfg, _params, prog = toy
    rs = np.random.RandomState(11)
    work = [(rs.randint(0, VOCAB, n), m)
            for n, m in ((3, 6), (7, 5), (1, 4), (5, 1), (4, 9))]
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        reqs = [eng.submit(p, max_new_tokens=m) for p, m in work]
        outs = [r.result(timeout=60)[0].tolist() for r in reqs]
        doomed = eng.submit(np.array([1, 2], np.int32), max_new_tokens=13,
                            deadline=0.001)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30)
    st = eng.stats()        # closed: the worker has written its last
    d, steps = st["decode"], st["counters"]["steps"]
    assert 0 <= d["steps_starved"] <= d["steps_overlapped"] < steps
    assert 0 <= d["fetches_waited"] <= steps
    assert d["steps_starved"] + d["fetches_waited"] <= 2 * steps
    assert d["stalls"] >= 0 and d["stall_seconds"] >= 0.0
    by_name = {}
    for name, attrs in spans_seen:
        by_name.setdefault(name, []).append(attrs)
    dispatched = by_name["serve/decode_step"]
    fetched = [a for a in by_name["serve/fetch"] if a]
    # every step taken in was fetched once, by its number
    assert sorted(a["batch"] for a in fetched) \
        == sorted(a["batch"] for a in dispatched)[:len(fetched)]
    assert len(fetched) == steps
    assert sum(1 - a["ready"] for a in fetched) == d["fetches_waited"]
    # a step that starts a pipeline has nothing in flight to ask after
    for a in dispatched:
        assert ("prev_ready" in a) == bool(a["in_flight"])
    assert sum(a.get("prev_ready", 0) for a in dispatched
               if a["batch"] in {f["batch"] for f in fetched}) \
        == d["steps_starved"]
    assert sum(a["decoded"] for a in by_name["serve/retire"]) \
        == d["tokens_decoded"] \
        == sum(m for _p, m in work) + len(doomed.generated)
    done = by_name["serve/request_done"]
    ok = [a for a in done if a["outcome"] == "ok"]
    assert sorted((a["n_prompt"], a["n_generated"]) for a in ok) \
        == sorted((len(p), len(o)) for (p, _m), o in zip(work, outs))
    for a in ok:
        assert 0 <= a["queue_wait_us"] <= a["ttft_us"] <= a["total_us"]
        assert ("itl_max_us" in a) == (a["n_generated"] > 1)
    # the doomed request was settled by the engine (its span says on what)
    # or shed by the queue before it saw a slot (no span)
    rest = [a for a in done if a["outcome"] != "ok"]
    assert [a["outcome"] for a in rest] in ([], ["DeadlineExceeded"])
    assert sum(a["retired"] for a in by_name["serve/retire"]) == len(ok)


def test_a_slowed_step_counts_as_a_stall(toy, spans_seen):
    """(g) A step that takes far longer than the steps before it (here the
    straggler drill's sleep before step 9's dispatch, which the fetch of
    step 8 waits out) is a stall: counted, its seconds summed, and named on
    the ``serve/retire`` that took it in."""
    from mxnet_tpu.resilience import chaos
    cfg, _params, prog = toy
    try:
        with DecodeEngine(prog, default_deadline=60.0) as eng, \
                chaos.inject("slow_exec", at_step=9, seconds=0.5):
            eng.generate(np.arange(4) % VOCAB, max_new_tokens=10)
        st = eng.stats()
    finally:
        chaos.reset()
    stalled = [a["stalled_ms"] for name, a in spans_seen
               if name == "serve/retire" and "stalled_ms" in a]
    assert st["decode"]["stalls"] == len(stalled) >= 1
    assert max(stalled) >= 500.0
    assert st["decode"]["stall_seconds"] == pytest.approx(
        sum(stalled) / 1e3, abs=1e-3)


def test_a_result_without_is_ready_counts_as_ready(toy, monkeypatch):
    """(h) A step that hands back host arrays (a test's wrapper) has
    nothing to wait for: every dispatch behind another counts as starved,
    no fetch as a wait."""
    cfg, _params, prog = toy
    inner = prog.step

    def step(*args):
        out = inner(*args)
        return (np.asarray(out[0]),) + tuple(out[1:])

    monkeypatch.setattr(prog, "step", step)
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        out = eng.generate(np.arange(4) % VOCAB, max_new_tokens=6)
    st = eng.stats()
    assert out.tolist() == _greedy_alone(prog, np.arange(4) % VOCAB, 6)
    assert st["counters"]["steps"] == 4 + 6 - 1
    assert st["decode"]["steps_starved"] \
        == st["decode"]["steps_overlapped"] == 4 + 6 - 2
    assert st["decode"]["fetches_waited"] == 0


def test_quantized_engine_logit_kl_probe(toy):
    """int8/int4 weight-only quantization stays within the quality
    probe: bounded max-KL between f32 and quantized next-token
    distributions on the toy transformer."""
    cfg, params, prog = toy
    lf = _first_logits(prog)
    pf = np.exp(lf - lf.max(-1, keepdims=True))
    pf /= pf.sum(-1, keepdims=True)
    for q, bound in (("int8", 1e-3), ("int4", 0.1)):
        pq = DecodeProgram(params, cfg, quantize=q, name="toy-" + q)
        lq = _first_logits(pq)
        pqs = np.exp(lq - lq.max(-1, keepdims=True))
        pqs /= pqs.sum(-1, keepdims=True)
        kl = float((pf * (np.log(pf + 1e-12)
                          - np.log(pqs + 1e-12))).sum(-1).max())
        assert kl < bound, (q, kl)


def test_export_load_roundtrip_and_topology(toy, tmp_path):
    cfg, params, _prog = toy
    pq = DecodeProgram(params, cfg, quantize="int8", name="exp")
    path = str(tmp_path / "decode.mxt")
    pq.export(path)
    loaded = DecodeProgram.load(path)
    assert loaded.config.quantize == "int8"
    assert np.array_equal(_first_logits(loaded), _first_logits(pq))
    # a mesh this host cannot satisfy is refused typed, pre-deserialize
    with pytest.raises(TopologyMismatch):
        DecodeProgram.load(path, mesh={"tp": 4096})
    # refuse a non-decode container
    from mxnet_tpu.resilience.container import write_container
    bad = str(tmp_path / "bad.mxt")
    write_container(bad, arrays={}, meta={"magic": "nope"}, blobs={})
    with pytest.raises(mx.base.MXNetError):
        DecodeProgram.load(bad)


def test_gc307_clean_and_seeded(toy):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.analysis.graphcheck import check_decode_retrace
    cfg, _params, prog = toy
    # the paged step is clean: identical trace across positions AND
    # batch membership
    rep = decode_retrace_report(prog)
    assert not rep.findings, rep.pretty()

    # seeded: cache grown by concatenation -> shapes retrace per token
    D = 16
    W = np.random.RandomState(0).randn(D, D).astype(np.float32)

    def naive_grow(cache_k, x):
        k = x @ W
        cache = jnp.concatenate([cache_k, k[None]], axis=0)
        return cache, cache @ k
    a = (jnp.zeros((40, D), np.float32), jnp.zeros((D,), np.float32))
    b = (jnp.zeros((41, D), np.float32), jnp.zeros((D,), np.float32))
    rep = check_decode_retrace(naive_grow, a, b, target="grow")
    assert [f.rule for f in rep.findings] == ["GC307"]

    # seeded: position coerced to a host int -> static cache key
    def naive_pos(cache, k, pos):
        cache = jax.lax.dynamic_update_slice(cache, k[None],
                                             (int(pos), 0))
        return cache, cache @ k
    cache = jnp.zeros((64, D), np.float32)
    k = jnp.zeros((D,), np.float32)
    rep = check_decode_retrace(naive_pos, (cache, k, 3), (cache, k, 4),
                               target="baked")
    assert [f.rule for f in rep.findings] == ["GC307"]

    # a non-decode-shaped program passes silently (the rule can sit on
    # generic entry points)
    def plain(x):
        return (x @ W).sum()
    rep = check_decode_retrace(plain, (jnp.zeros((4, D), np.float32),),
                               (jnp.zeros((4, D), np.float32),))
    assert not rep.findings


def test_tp2_parity_and_collective_audit(toy):
    """Tensor-parallel serving: the tp2-sharded step matches the
    single-device logits, and its lowered HLO moves EXACTLY the
    analytic per-axis collective bytes (2 activation reductions per
    layer + one logits gather — nothing scales with weights or cache)."""
    import jax
    from mxnet_tpu.parallel.audit import collective_accounting
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg, params, prog = toy
    # vocab 29 is not tp-divisible: the head degrades to replicated and
    # the model drops the gather — parity must still hold
    p2 = DecodeProgram(params, cfg, mesh={"tp": 2}, name="tp2")
    l1, l2 = _first_logits(prog), _first_logits(p2)
    assert np.abs(l1 - l2).max() < 1e-4
    acct = collective_accounting(p2.lowered_step_text(),
                                 mesh=p2.spec.mesh)
    model = decode_tp_model_bytes(cfg, 2)
    measured = {k: v["bytes"] for k, v in acct.items()}
    assert measured == model, (measured, model)
    # a tp-divisible vocab shards the head: the ONE logits all-gather
    # joins the model, still at exactly the analytic bytes, and every
    # byte is attributed to the tp axis
    cfg32 = DecodeConfig(32, L, H, HEADS, T, page_size=4, max_seqs=3)
    p32 = DecodeProgram(init_decode_params(cfg32, seed=3), cfg32,
                        mesh={"tp": 2}, name="tp2-v32")
    acct32 = collective_accounting(p32.lowered_step_text(),
                                   mesh=p32.spec.mesh)
    model32 = decode_tp_model_bytes(cfg32, 2)
    assert {k: v["bytes"] for k, v in acct32.items()} == model32
    for kind, info in acct32.items():
        assert set(info["by_axis"]) == {"tp"}, (kind, info)


def test_tp2_engine_kill_swap_drill(toy):
    """The serving drill on a tp2-served decode model: a model swap
    lands mid-generation without a failed or late request, and an
    executor kill burst (chaos exec_error) sheds typed with ZERO late
    OKs; the page pool drains clean."""
    import jax
    from mxnet_tpu.resilience import chaos
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg, params, _prog = toy
    p_a = DecodeProgram(params, cfg, mesh={"tp": 2}, name="drill-a")
    p_b = DecodeProgram(init_decode_params(cfg, seed=9), cfg,
                        mesh={"tp": 2}, name="drill-b")
    deadline = 30.0
    with DecodeEngine(p_a, default_deadline=deadline,
                      breaker_threshold=100) as eng:
        rs = np.random.RandomState(0)
        reqs = [eng.submit(rs.randint(0, VOCAB, 2 + i % 3),
                           max_new_tokens=8) for i in range(6)]
        # rolling swap mid-generation: validated+compiled OFF the flip
        eng.swap(p_b)
        assert eng._program is p_b
        ok = late = 0
        for r in reqs:
            out = r.result(timeout=30)       # must ALL complete OK
            assert out[0].size == 8
            assert r.latency <= deadline
            ok += 1
        # kill burst: every step fails while armed -> typed ExecFailed,
        # never a late OK, pool freed
        with chaos.inject("exec_error", count=50):
            doomed = [eng.submit(rs.randint(0, VOCAB, 3),
                                 max_new_tokens=4, deadline=5.0)
                      for _ in range(3)]
            for r in doomed:
                with pytest.raises(Exception) as ei:
                    r.result(timeout=30)
                assert type(ei.value).__name__ in (
                    "ExecFailed", "DeadlineExceeded", "CircuitOpen")
        chaos.reset()
        st = eng.stats()
        assert st["decode"]["pages_free"] == st["decode"]["pages_total"]
        assert ok == 6 and late == 0
    # one executable each, though b's first steps were fed a's tokens
    assert p_a.trace_count == 1 and p_b.trace_count == 1
    # ... and one EXECUTABLE: with both programs warm, a pipeline started
    # from scratch (the stand-in for prev_tok, then the step's own tokens,
    # a fresh pool, then the step's own) compiles nothing
    import jax.monitoring
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiled.append(event))
    for p in (p_a, p_b):
        with DecodeEngine(p, default_deadline=deadline) as eng:
            assert eng.generate(rs.randint(0, VOCAB, 3),
                                max_new_tokens=4).size == 4
    assert not [e for e in compiled if "backend_compile" in e]
    assert p_a.trace_count == 1 and p_b.trace_count == 1
    # geometry mismatch is refused with the old model still serving
    cfg2 = DecodeConfig(VOCAB, L, H, HEADS, T * 2, page_size=4,
                        max_seqs=cfg.max_seqs)
    with DecodeEngine(p_b) as eng2:
        with pytest.raises(SwapFailed):
            eng2.swap(DecodeProgram(init_decode_params(cfg2), cfg2))


def test_kv_cache_memory_tag(toy, monkeypatch):
    from mxnet_tpu.telemetry import memory as tmem
    assert "kv_cache" in tmem.TAGS
    cfg, _params, prog = toy
    monkeypatch.setenv("MXNET_TPU_MEMWATCH", "1")
    tmem.reset()
    try:
        kv = prog.fresh_cache()
        assert tmem.live_bytes_by_tag().get("kv_cache", 0) \
            >= prog.cache_bytes
        del kv
    finally:
        monkeypatch.delenv("MXNET_TPU_MEMWATCH", raising=False)
        tmem.reset()


def test_decode_autotune_record_and_read(tmp_path, monkeypatch):
    from mxnet_tpu.ops import autotune
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune.invalidate()
    try:
        # no entry: platform default (xla on cpu)
        assert autotune.decode_backend(2, 2, 8, 4, "float32") == "xla"
        autotune.record("decode_attn", (2, 2, 8, 4, "float32"), "pallas",
                        0.5)
        assert autotune.decode_backend(2, 2, 8, 4, "float32") == "pallas"
        # the kernel wrapper consults the cache under auto
        monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "auto")
        rs = np.random.RandomState(0)
        q = rs.randn(2, 2, 8).astype(np.float32)
        kp = rs.randn(5, 2, 4, 8).astype(np.float32)
        pt = np.zeros((2, 1), np.int32)
        lens = np.array([2, 1], np.int32)
        out = pk.decode_attention(q, kp, kp, pt, lens)   # pallas path
        assert np.isfinite(np.asarray(out)).all()
    finally:
        autotune.invalidate()


@pytest.mark.slow
def test_servebench_decode_smoke(capsys):
    # @slow per the PR-16 tier-1 re-profile: the continuous-vs-static
    # occupancy comparison depends on open-loop arrival timing, and on
    # the loaded 1-core rig arrivals bunch up enough for static batching
    # to tie (observed 0.671 vs 0.700 under a full-suite run); the
    # compile-once invariant it also guards stays in tier-1 via
    # test_engine_continuous_batching_parity_and_compile_once
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import servebench
    rc = servebench.main([
        "--decode", "--json", "--requests", "12",
        "--decode-prompts", "2,10", "--decode-new", "2,12",
        "--decode-layers", "1", "--decode-hidden", "32",
        "--decode-heads", "2", "--decode-vocab", "64",
        "--decode-seq", "32", "--decode-page", "8",
        "--decode-slots", "2", "--deadline", "0"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["compiles"] == 1
    cont, stat = report["continuous"], report["static"]
    assert cont["tokens"] == stat["tokens"] > 0
    assert not cont["errors"]
    # continuous batching refills freed slots: strictly better occupancy
    # on a mixed-length stream (throughput follows on real accelerators;
    # on a loaded CI box wall-clock is too noisy to gate hard)
    assert cont["occupancy_mean"] > stat["occupancy_mean"]
    assert report["continuous_vs_static"] > 0.7
