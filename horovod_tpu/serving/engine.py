"""Continuous-batching decode engine over the paged KV cache.

Glues the three layers below it into one `step()`:

- the scheduler admits/evicts (scheduler.py),
- admitted prompts prefill through ``llama_prefill`` (one compiled
  pass -> first token + per-layer K/V, written into pool blocks),
- running sequences decode through ``llama_decode_step`` — ONE jitted
  program with STATIC shapes: the batch is padded to ``max_batch`` rows
  and every gathered cache to ``max_context`` rounded up to whole
  blocks, raggedness carried by the ``lengths`` mask. Static shapes buy
  two things: no retrace as the batch composition churns (admissions /
  completions / evictions every step), and bit-deterministic numerics
  regardless of WHICH requests happen to share a step — the property
  the elastic re-queue guarantee (token-identical replay on survivors)
  and eviction-replay both lean on.

Padding rows decode a dummy token at length 0 (self-attention over one
position — numerically inert, output discarded); their cost is bounded
by max_batch, the knob the operator already sized for peak.
"""

import numpy as np

from horovod_tpu.serving.kvcache import PagedKVCache
from horovod_tpu.serving.scheduler import ContinuousBatchingScheduler
from horovod_tpu.telemetry import reqtrace


class DecodeEngine:
    """Single-rank continuous-batching decode over a paged pool."""

    def __init__(self, params, config, *, block_size=16, n_blocks=256,
                 max_batch=8, max_context=512, token_budget=None,
                 quantized=False):
        import jax.numpy as jnp

        from horovod_tpu.models.generate import require_decodable

        require_decodable(config)
        self.params = params
        self.config = config
        self._jnp = jnp
        self.max_batch = int(max_batch)
        # Static gathered-cache length: whole blocks covering
        # max_context (+1 growth slot so a sequence at exactly
        # max_context-1 still fits its next token).
        # compute_dtype is a numpy-compatible dtype object (ml_dtypes
        # covers bfloat16), so the pool can store it directly.
        self.pool = PagedKVCache(
            config.n_layers, config.n_kv_heads, config.head_dim,
            block_size=block_size, n_blocks=n_blocks,
            dtype=config.compute_dtype, quantized=quantized)
        self.blocks_per_seq = self.pool.blocks_for(int(max_context))
        self.s_pad = self.blocks_per_seq * self.pool.block_size
        self.scheduler = ContinuousBatchingScheduler(
            self.pool, max_batch=max_batch,
            token_budget=int(token_budget) if token_budget
            else self.s_pad * max_batch)
        self.steps = 0
        self.tokens_out = 0

    # ---- admission ----------------------------------------------------

    def submit(self, req):
        """Queue a request for local prefill+decode (the all-in-one
        lane; the disaggregated service prefills remotely and calls
        :meth:`adopt_remote` instead)."""
        if len(req.prompt) + req.max_new_tokens > self.s_pad:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"max_new {req.max_new_tokens} exceeds max_context "
                f"{self.s_pad}")
        self.scheduler.submit(req)

    def prefill(self, req):
        """Run the compiled prefill for one request; returns
        (first_token, k, v [L, Hkv, T, D] numpy)."""
        from horovod_tpu.models.generate import llama_prefill

        prompt = self._jnp.asarray(
            np.asarray(req.prompt, np.int32)[None, :])
        first, ck, cv = llama_prefill(self.params, prompt, self.config)
        # [L, 1, Hkv, T, D] -> [L, Hkv, T, D]
        return (int(np.asarray(first)[0]), np.asarray(ck[:, 0]),
                np.asarray(cv[:, 0]))

    def _admit_local(self):
        for seq in self.scheduler.admit():
            reqtrace.record_request("prefill", seq.rid,
                                    aux=len(seq.req.prompt))
            first, k, v = self.prefill(seq.req)
            self.pool.write(seq.blocks, 0, k, v)
            seq.generated.append(first)
            self.tokens_out += 1
            if seq.done:  # max_new_tokens == 1: prefill finished it
                self.scheduler.complete(seq)
            else:
                reqtrace.record_request("decode_wait", seq.rid)

    def adopt_remote(self, seq):
        """Register a sequence whose blocks were shipped in (service
        lane). The caller allocated+wrote the blocks already."""
        reqtrace.record_request("decode_wait", seq.rid,
                                aux=len(seq.blocks))
        self.scheduler.adopt(seq)

    # ---- the decode step ----------------------------------------------

    def step(self):
        """One continuous-batching step: admit, then one token for
        every running sequence. Returns [(rid, token, done), ...]."""
        self._admit_local()
        # ensure_slot may EVICT other running sequences (pool
        # pressure), so iterate a snapshot and re-validate membership
        # afterwards — a sequence granted a slot early can still be
        # evicted by a later sibling's growth.
        snapshot = list(self.scheduler.running)
        for seq in snapshot:
            if seq in self.scheduler.running:
                self.scheduler.ensure_slot(seq)
        live = [s for s in snapshot if s in self.scheduler.running]
        if not live:
            return []
        live = live[:self.max_batch]
        # Request tracing: this batch's rows are DECODING for the span
        # of the jitted step; survivors fall back to decode_wait after
        # it. One transition pair per row per step is the ledger's
        # resolution (tail_report aggregates the alternation), cheap
        # enough that serving/bench_lane.py's serving_trace_overhead
        # row holds the whole tracing cost under 2% of sustained tok/s.
        for seq in live:
            reqtrace.record_request("decode_active", seq.rid,
                                    aux=seq.cached)
        out = self._decode_batch(live)
        events = []
        for seq, tok in zip(live, out):
            # Write the new token's K/V before appending: position
            # `length` is the slot ensure_slot just guaranteed.
            seq.generated.append(tok)
            self.tokens_out += 1
            events.append((seq.rid, tok, seq.done))
            if seq.done:
                self.scheduler.complete(seq)
            else:
                reqtrace.record_request("decode_wait", seq.rid)
        self.steps += 1
        return events

    def _decode_batch(self, live):
        from horovod_tpu.models.generate import llama_decode_step

        jnp = self._jnp
        c = self.config
        b_pad = self.max_batch
        s_pad = self.s_pad
        dt = c.compute_dtype
        quant = self.pool.quantized
        store = np.int8 if quant else dt
        tokens = np.zeros(b_pad, np.int32)
        lengths = np.zeros(b_pad, np.int32)
        ck = np.zeros((c.n_layers, b_pad, c.n_kv_heads, s_pad,
                       c.head_dim), store)
        cv = np.zeros_like(ck)
        ks = vs = None
        if quant:
            ks = np.zeros((c.n_layers, b_pad, c.n_kv_heads, s_pad),
                          np.float32)
            vs = np.zeros_like(ks)
        for i, seq in enumerate(live):
            tokens[i] = seq.generated[-1]
            lengths[i] = seq.cached
            k, v, k_s, v_s = self.pool.gather(
                seq.blocks, pad_blocks=self.blocks_per_seq
                - len(seq.blocks))
            ck[:, i], cv[:, i] = k, v
            if quant:
                ks[:, i], vs[:, i] = k_s, v_s
        nxt, k_new, v_new = llama_decode_step(
            self.params, jnp.asarray(tokens), jnp.asarray(ck),
            jnp.asarray(cv), jnp.asarray(lengths), c,
            k_scale=jnp.asarray(ks) if quant else None,
            v_scale=jnp.asarray(vs) if quant else None)
        nxt = np.asarray(nxt)
        k_new = np.asarray(k_new, np.float32 if quant else dt)
        v_new = np.asarray(v_new, np.float32 if quant else dt)
        for i, seq in enumerate(live):
            # [L, Hkv, D] -> [L, Hkv, 1, D]: the input token's K/V
            # lands at the slot ensure_slot just guaranteed.
            self.pool.write(seq.blocks, seq.cached,
                            k_new[:, i][:, :, None, :],
                            v_new[:, i][:, :, None, :])
        return [int(t) for t in nxt[:len(live)]]

    # ---- drive to completion (bench / offline lane) --------------------

    def serve_alone(self, req):
        """``req``'s tokens from this engine with nothing beside it and
        nothing lost: the uninterrupted run the elastic replay is held
        to. Static shapes make it what ANY batch composition gives.
        With an int8 pool it is NOT ``llama_generate``'s continuation
        wherever two logits lie within the quantization error (the
        tiny test model: 2.6081 against 2.6000 of a spread of 5.8);
        prompt and first token, computed before any cache is read,
        always are."""
        self.submit(req)
        return self.run_until_idle()[req.rid]

    def run_until_idle(self, max_steps=100000):
        """Decode until nothing is waiting or running. Returns the
        completed {rid: tokens} map."""
        steps = 0
        while self.scheduler.waiting or self.scheduler.running:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("run_until_idle: no convergence "
                                   f"after {max_steps} steps")
        return {rid: s.tokens for rid, s in
                self.scheduler.completed.items()}
