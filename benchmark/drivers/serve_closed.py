"""Driver ``serve_closed``: ``DecodeEngine.submit()`` -> ``result()`` under a
closed loop of callers, each sending its next request when the last returns
(the closed loop is ``tools/servebench.run_closed``'s, copied).

The first round is made of requests caught in progress (chosen in proportion
to their length, at a uniform point of their life), which is what the slots
of a steady closed loop hold at a random moment, so the window opens on a
steady state and not on a lockstep start.  Once the window has closed and the engine is gone, the
plain reference runs once over a sample of the finished requests (the longest
among them): prompt and served tokens together, teacher-forced, and the
number compared is the widest gap by which a served token's logit lies below
the reference's best.  All requests are greedy: the engine has no sampler.
"""
import inspect
import threading
import time

import numpy as np

from benchmark.lib.traffic import RequestStream


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.cfg, ctx.traffic
        self.adapter = ctx.files.module("adapters", self.cfg["family"])
        self.ref = ctx.files.module("refs", self.cfg["family"])
        self.spans = ctx.spans
        self.lock = threading.Lock()
        self.done = []              # (t_submit, t_result, prompt, ids|error)
        self.stop = False
        self.attended = 0           # sum of seq_lens over the steps seen
        self.submitted_tokens = 0   # prompt + answer lengths of all sent

    # -- set-up ------------------------------------------------------------
    def setup(self):
        from mxnet_tpu.serving.decode import DecodeEngine
        cfg, traffic = self.cfg, self.traffic
        weights = {k: np.asarray(v) for k, v in
                   self.ref.make_weights(cfg, self.ctx.seed).items()}
        self.prog = self.adapter.decode_program(cfg, traffic, weights)
        del weights
        self._wrap_step()
        self.engine = DecodeEngine(self.prog)
        self.stream = RequestStream(traffic, cfg["vocab_size"],
                                    cfg["n_positions"], self.ctx.seed)
        first_round = [self.stream.take_in_progress()
                       for _ in range(traffic["clients"])]
        self.threads = [threading.Thread(target=self._client, args=(first,),
                                         daemon=True)
                        for first in first_round]
        for t in self.threads:
            t.start()
        time.sleep(traffic["warm_seconds"])

    def _wrap_step(self):
        """Names every dispatch in the trace and sums the contexts attended
        (the step's ``seq_lens``), which only the per-layer readers
        ``decode_attn_roofline``, ``mfu_pct.serve`` and ``kv_pool_live_pct``
        use: no end-to-end number passes through here.  A step that no
        longer takes ``seq_lens`` by that name leaves those readers with
        nothing to read, and the run goes on."""
        inner = self.prog.step
        names = inspect.signature(inner).parameters

        def step(*args, **kwargs):
            seq_lens = kwargs.get("seq_lens")
            if seq_lens is None and "seq_lens" in names:
                at = list(names).index("seq_lens")
                seq_lens = args[at] if at < len(args) else None
            if seq_lens is None:
                self.attended = None
            elif self.attended is not None:
                self.attended += int(np.sum(seq_lens))
            with self.spans.span("step_dispatch"):
                return self.produced(inner(*args, **kwargs))

        self.prog.step = step

    def produced(self, out):
        """What a step hands back to the engine (the tests' subclass alters
        the tokens here)."""
        return out

    def _client(self, first):
        prompt, max_new = first
        while not self.stop:
            with self.lock:
                self.submitted_tokens += len(prompt) + max_new
            t0 = time.perf_counter()
            try:
                req = self.engine.submit(prompt, max_new_tokens=max_new)
                ids = np.asarray(req.result(timeout=120.0)[0])
                if len(ids) != max_new:
                    raise RuntimeError("%d tokens for max_new %d"
                                       % (len(ids), max_new))
            except Exception as e:      # a failed request is counted
                if self.stop:
                    return
                ids = e
            t1 = time.perf_counter()
            with self.lock:
                self.done.append((t0, t1, prompt, ids))
                prompt, max_new = self.stream.take()

    # -- the window --------------------------------------------------------
    def _counters(self):
        st = self.engine.stats()
        return {"steps": st["counters"]["steps"],
                "prefilled": st["decode"]["tokens_prefilled"],
                "decoded": st["decode"]["tokens_decoded"],
                "attended": self.attended}

    def _measure(self, seconds):
        self.spans.reset()
        before = self._counters()
        t0 = time.perf_counter()
        time.sleep(seconds)
        t1 = time.perf_counter()
        after = self._counters()
        with self.lock:
            done = [d for d in self.done if t0 <= d[1] <= t1]
        delta = {k: (after[k] - before[k] if after[k] is not None else None)
                 for k in after}
        return t0, t1, done, delta

    def window(self, seconds):
        t0, t1, done, delta = self._measure(seconds)
        ok = [d for d in done if not isinstance(d[3], Exception)]
        self.finished = ok
        work = self.adapter.serve_work(self.cfg, delta)
        return {"window_s": t1 - t0, "attempted": len(done),
                "failed": len(done) - len(ok),
                "engine": delta, "serve_work": work,
                "host_seconds": dict(self.spans.seconds)}

    def traced_segment(self, seconds):
        t0, t1, _done, delta = self._measure(seconds)
        return {"window_s": t1 - t0, "engine": delta,
                "serve_work": self.adapter.serve_work(self.cfg, delta),
                "host_seconds": dict(self.spans.seconds)}

    def release(self):
        # the engine's counts since it started, between what the callers
        # know it has finished (read first) and all they sent (read last)
        with self.lock:
            finished = sum(len(d[2]) + len(d[3]) - 1 for d in self.done
                           if not isinstance(d[3], Exception))
        counted = self._counters()
        with self.lock:
            self.token_counts = (finished, counted["prefilled"]
                                 + counted["decoded"], self.submitted_tokens)
        self.stop = True
        self.engine.close()
        for t in self.threads:
            t.join(timeout=30.0)
        self.engine = self.prog = None

    # -- the comparison ----------------------------------------------------
    def sample(self):
        """Finished requests drawn from the seed, the longest among them."""
        n = min(self.traffic["checked_requests"], len(self.finished))
        if n == 0:
            return []
        total = [len(d[2]) + len(d[3]) for d in self.finished]
        longest = int(np.argmax(total))
        rs = np.random.default_rng([self.ctx.seed, 4])
        picks = set(rs.permutation(len(self.finished))[:n - 1].tolist())
        picks.add(longest)
        return [(self.finished[i][2], np.asarray(self.finished[i][3]))
                for i in sorted(picks)]

    def verify(self, cast=None):
        sample = self.sample()
        gap, n_tokens, where = self.ref.served_token_gap(
            self.cfg, self.ctx.seed, sample,
            self.traffic["reference_rows_per_block"], cast=cast)
        return [("served_logit_gap", gap, self.ctx.limits["served_logit_gap"],
                 "%s over %d served tokens of %d requests"
                 % (where, n_tokens, len(sample))),
                ("requests_checked_short", float(len(sample) == 0), 0.0, None),
                ("tokens_miscounted", self.tokens_miscounted(), 0.0,
                 "finished %d <= counted %d <= sent %d" % self.token_counts)]

    def tokens_miscounted(self):
        """By how many tokens the engine's ``tokens_prefilled`` +
        ``tokens_decoded`` (what ``serve_tokens_s`` reads) lie outside what
        the callers themselves know: no fewer than the requests that came
        back needed (prompt + answer - 1 each), no more than all that were
        sent hold."""
        finished, counted, sent = self.token_counts
        return float(max(0, finished - counted, counted - sent))
