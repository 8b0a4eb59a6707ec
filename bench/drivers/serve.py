"""A served model: open-loop requests into ``ServeEngine.serve``.

Set-up makes the weights on the device from the seed (one jitted call,
in the configuration's dtype, laid out as the program's ``Model.init``
lays them out, which is checked), draws every request of the window,
opens a ``ServeEngine`` over a fresh request log, and serves one batch
of every (batch, prompt length) the window can use.

The window is a single-threaded front end.  Requests become due at
their arrival times; whenever requests are waiting, it takes the prompt
length of the oldest one and hands ``serve`` that length's waiting
requests, oldest first, as many as the largest power of two up to the
batch size (``serve`` batches equal lengths and compiles each (batch,
length) shape apart).  When none is waiting it sleeps until the next
arrival.  Once the window's time is up nothing more is sent; the call
in flight returns, and ``gen_tok_s`` is the new tokens of every call
sent before the close over the time from the window's start to the
return of the last of them.  A request's latency runs from its arrival
time to the return of the ``serve`` call that committed it; where the
cell reports the tail ``req_p80_ms``, requests due in the window that
are still waiting when it closes are served after it and count.

Checks: every request answered with ``new_tokens`` tokens in the
vocabulary; every answer read back, the same, from a ``RequestLog``
reopened on the log directory; and a sample of the answers, drawn from
the seed with the longest prompt in it, against the plain reference:
the widest gap by which a served token's logit lies below the
reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import shutil
import tempfile
import time

import numpy as np

from bench import harness as H
from bench import traffic as T

WARM_RID0 = 1 << 30   # rids of set-up requests, apart from the window's
NO_ANSWER = 1e9       # the logit gap reported where no answer came back
SAMPLE_REQUESTS = 8   # answered requests compared with the reference


def program_arch(config: dict):
    """The program's architecture at the configuration's sizes."""
    from repro.configs.registry import parse_arch
    return dataclasses.replace(
        parse_arch(config["program_arch"]),
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["torch_dtype"],
        compute_dtype=config["torch_dtype"])


def reference_module(config: dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def make_params(config: dict, key, padded_vocab: int):
    """The weights in the program's layout (traceable: jit it)."""
    import jax
    import jax.numpy as jnp
    ref = reference_module(config)
    L = config["num_hidden_layers"]
    lw = jax.vmap(lambda i: ref.layer_weights(config, key, i))(
        jnp.arange(L))
    embed = ref.embed_weights(config, key)
    embed = jnp.pad(embed, ((0, padded_vocab - embed.shape[0]), (0, 0)))
    return {"embed": embed,
            "final_norm": ref.final_norm_weights(config, key),
            "blocks": {"ln1": lw["ln1"], "ln2": lw["ln2"],
                       "attn": {"wq": lw["q"], "wk": lw["k"], "wv": lw["v"],
                                "wo": lw["o"], "q_norm": lw["q_norm"],
                                "k_norm": lw["k_norm"]},
                       "mlp": {"w_gate": lw["gate"], "w_up": lw["up"],
                               "w_down": lw["down"]}}}


def pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1)


@dataclasses.dataclass
class Call:
    start_ns: int
    end_ns: int
    batch: int
    length: int


class Driver:
    def __init__(self, cell, seed: int, devices, seconds: float):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.devices = devices
        self.seconds = float(seconds)
        self.batch = int(self.config["serve"]["batch_size"])
        self.calls = []
        self.spans = []
        self.rid_base = 0    # request r of the window gets rid rid_base + r
        # a cell judged on a tail has every request due in the window
        # served, those still waiting at its close after it, so that the
        # tail is the tail of all of them; otherwise nothing is sent after
        # the close
        self.drain = any(m["name"] == "req_p80_ms"
                         for m in cell.end_to_end())

    # ------------------------------------------------------------------ #
    def setup(self) -> None:
        import jax
        from repro.models.model import build_model, padded_vocab
        from repro.serving.engine import ServeEngine
        cfg = self.config
        ref = reference_module(cfg)
        self.model = build_model(program_arch(cfg))
        key = ref.seed_key(self.seed)
        vp = padded_vocab(self.model.cfg)
        make = jax.jit(lambda k: make_params(cfg, k, vp))
        want = jax.eval_shape(self.model.init, key)
        got = jax.eval_shape(make, key)
        if (jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got)))):
            raise RuntimeError("the benchmark's weights do not have the "
                               "layout of the program's Model.init")
        self.params = make(key)
        jax.block_until_ready(self.params)

        self.reqs = T.open_loop(self.traffic, self.seed, self.seconds,
                                cfg["vocab_size"])
        lengths = sorted({int(s) for s in self.traffic["lengths"]})
        self.new_tokens = self.reqs.new_tokens
        self.log_dir = tempfile.mkdtemp(prefix="bench_serve_log_")
        self.engine = ServeEngine(
            self.model, self.params, log_dir=self.log_dir,
            max_len=max(lengths) + self.new_tokens, batch_size=self.batch)
        rng = np.random.default_rng([self.seed, 5])
        rid = WARM_RID0
        for S in lengths:
            b = 1
            while b <= self.batch:
                reqs = {}
                for _ in range(b):
                    reqs[rid] = rng.integers(0, cfg["vocab_size"], S,
                                             dtype=np.int32)
                    rid += 1
                self.engine.serve(reqs, n_new=2)
                b *= 2

    # ------------------------------------------------------------------ #
    def window(self, seconds: float, tw) -> None:
        import jax
        R = self.reqs
        n = len(R.lengths)
        lengths = R.lengths
        drain = self.drain
        t0 = time.perf_counter()
        self.t0_ns = time.perf_counter_ns()
        close = t0 + seconds
        due = t0 + R.arrival_s
        self.finish = np.full(n, np.nan)
        self.sent = np.zeros(n, bool)
        self.tokens = [None] * n
        self.backlog = []        # (seconds into the window, waiting)
        pending, nxt = [], 0
        closed_tokens = 0        # tokens of the calls sent before the close
        self.t_last = t0         # return of the last call sent before it
        late = 0.0
        while nxt < n or pending:
            if tw is not None:
                tw.at_boundary(time.perf_counter() - t0)
            now = time.perf_counter()
            if now >= close and not drain:
                break
            while nxt < n and due[nxt] <= now:
                pending.append(nxt)
                nxt += 1
            if not pending:
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    dt = due[nxt] - time.perf_counter()
                    if not drain:
                        dt = min(dt, close - time.perf_counter())
                    if dt > 0:
                        time.sleep(dt)
                late = max(late, time.perf_counter() - due[nxt])
                continue
            S = lengths[pending[0]]
            same = [r for r in pending if lengths[r] == S][:self.batch]
            take = same[:pow2_floor(len(same))]
            self.backlog.append((now - t0, len(pending)))
            c0 = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("serve_call"):
                out = self.engine.serve(
                    {self.rid_base + int(r): R.prompts[r] for r in take},
                    n_new=self.new_tokens)
            c1 = time.perf_counter_ns()
            t_end = time.perf_counter()
            self.calls.append(Call(c0, c1, len(take), int(S)))
            if now < close:
                closed_tokens += len(take) * self.new_tokens
                self.t_last = t_end
            for r in take:
                self.sent[r] = True
                self.finish[r] = t_end
                self.tokens[r] = out.get(self.rid_base + int(r))
            taken = set(take)
            pending = [r for r in pending if r not in taken]
        self.t_close = close
        self.gen_tok_s = closed_tokens / (self.t_last - t0)
        self.latency_s = self.finish - due
        lat = self.latency_s[self.sent] * 1e3
        sizes = np.bincount([c.batch for c in self.calls],
                            minlength=self.batch + 1)
        H.log(f"serve: {int(self.sent.sum())} of {n} requests sent in "
              f"{len(self.calls)} calls (by batch size {sizes.tolist()}), "
              f"{len(pending) + n - nxt} waiting at the close; the front "
              f"end woke at most {late * 1e3:.3f} ms after an arrival")
        H.log(f"serve: gen_tok_s {self.gen_tok_s!r} over "
              f"{self.t_last - t0!r} s; latency of the sent, mean "
              f"{float(lat.mean())!r} p50 {float(np.percentile(lat, 50))!r} "
              f"p80 {float(np.percentile(lat, 80))!r} ms")
        tr = self.engine.tracer
        self.spans = [(tr.epoch_ns + int(r["t_us"] * 1e3),
                       tr.epoch_ns + int((r["t_us"] + r["dur_us"]) * 1e3),
                       r["span"], r.get("meta", {}))
                      for r in tr.records()]
        if self.spans and self.spans[0][0] > self.t0_ns:
            H.log("serve: the program's span ring dropped spans of the "
                  "window; span metrics read the newest only")

    def host_spans(self, lo_ns: int, hi_ns: int):
        """The program's spans inside [lo_ns, hi_ns], for idle attribution."""
        return [(s, e, name) for s, e, name, _ in self.spans
                if s >= lo_ns and e <= hi_ns]

    def window_spans(self, name: str):
        return [(s, e, meta) for s, e, n, meta in self.spans
                if n == name and s >= self.t0_ns]

    # ------------------------------------------------------------------ #
    def _answered(self, r: int) -> bool:
        t = self.tokens[r]
        return (t is not None and len(t) == self.new_tokens
                and all(0 <= x < self.config["vocab_size"] for x in t))

    def check(self) -> H.Outcome:
        from repro.serving.engine import RequestLog
        cfg = self.config
        sent = np.flatnonzero(self.sent)
        n = len(sent)
        answered = [int(r) for r in sent if self._answered(r)]
        e2e = {"gen_tok_s": self.gen_tok_s}
        if self.sent.all():
            e2e["req_p80_ms"] = float(np.percentile(self.latency_s * 1e3, 80))

        # the program's state goes before the reference runs
        self.engine = self.params = None
        gc.collect()

        log = RequestLog(self.log_dir)
        committed = log.committed()
        del log
        mismatch = sum(1 for r in answered
                       if committed.get(self.rid_base + r)
                       != list(self.tokens[r]))
        shutil.rmtree(self.log_dir, ignore_errors=True)

        gap = NO_ANSWER
        if answered:
            ref = reference_module(cfg)
            sample = self._sample(answered)
            prompts = [self.reqs.prompts[r] for r in sample]
            served = [np.asarray(self.tokens[r], np.int32) for r in sample]
            t = time.perf_counter()
            logits = ref.Reference(cfg, self.seed).served_logits(prompts,
                                                                 served)
            gap = ref.widest_gap(logits, served)
            self.last_sample = (prompts, served, logits)
            H.log(f"serve: reference over {len(sample)} requests, "
                  f"{sum(len(s) for s in served)} served tokens, "
                  f"{time.perf_counter() - t:.1f} s")
        limits = cfg["checks"]
        return H.Outcome(
            attempted=n, failed=n - len(answered),
            checks=[H.Check("unanswered", n - len(answered), 0),
                    H.Check("log_mismatch", mismatch, 0),
                    H.Check("logit_gap", gap, limits["logit_gap"])],
            end_to_end=e2e)

    def _sample(self, answered):
        """``SAMPLE_REQUESTS`` answered requests drawn from the seed, one
        of them with the longest prompt."""
        rng = np.random.default_rng([self.seed, 4])
        lens = self.reqs.lengths
        longest = [r for r in answered if lens[r] == lens[answered].max()]
        first = int(rng.choice(longest))
        rest = [r for r in answered if r != first]
        k = min(len(rest), SAMPLE_REQUESTS - 1)
        return [first] + [int(r) for r in rng.choice(rest, k, replace=False)]
