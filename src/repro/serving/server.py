"""Continuous-batching server core: dense or paged KV cache.

Grown out of ``launch/serve.py`` (which is now the CLI around this): a
fixed batch of decode *slots* advanced in lock-step by the planner's
sharded ``serve_step``, with per-request prefill at admission.  Two cache
modes:

- ``cache="dense"`` — the original layout: every slot owns ``max_len``
  KV rows from admission to finish.
- ``cache="paged"`` — the block/paged cache of DESIGN.md §9: slots hold
  pages from a shared pool through a block table
  (:mod:`repro.serving.paged_cache`), admission is gated on page
  availability, pages are appended as decode crosses page boundaries,
  and pool exhaustion preempts the youngest slot (its request re-queues
  and restarts).  Decode reads go through
  :func:`repro.models.transformer.decode_stack_paged` — bit-identical to
  the dense path in fp32 (``tests/test_serving.py``).

Prefill jit discipline: prompts are right-padded to power-of-two buckets
(min 8) so the jit cache holds O(log max_len) entries instead of one per
distinct prompt length; ``last_idx`` keeps the padded prefill exact
(logits read at the true last token, pad KV zeroed).

Decode hot path does exactly **one** host sync per step: a single
``np.asarray`` of the argmax'd next tokens for every slot at once.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sharding import use_rules
from repro.serving.paged_cache import (BlockTable, PageAllocator,
                                       PagedCacheConfig)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    preemptions: int = 0


def prompt_bucket(n: int, max_len: int, lo: int = 8) -> int:
    """Smallest power-of-two ≥ ``n`` (min ``lo``), capped at ``max_len`` —
    the padded prefill length.  Caps the jit cache at O(log max_len)."""
    if n > max_len:
        raise ValueError(f"prompt length {n} exceeds max_len {max_len}")
    b = lo
    while b < n:
        b <<= 1
    return min(b, max_len)


class Server:
    def __init__(self, model, plan, *, batch_slots: int, max_len: int,
                 eos_id: int = 1, cache: str = "dense", page_size: int = 0,
                 n_pages: int = 0, record_logits: bool = False):
        if cache not in ("dense", "paged"):
            raise ValueError(f"cache must be dense|paged, got {cache!r}")
        self.model = model
        self.plan = plan
        self.mesh = plan.mesh
        self.B = batch_slots
        self.max_len = max_len
        self.eos = eos_id
        self.cache = cache
        self.record_logits = record_logits
        self.last_logits: np.ndarray | None = None
        self._prefill_fns: dict = {}      # bucket → jitted prefill
        self.tokens = jnp.zeros((batch_slots,), jnp.int32)
        self.slots: list = [None] * batch_slots
        self.requeued: list = []          # preempted requests (paged)
        self.steps = 0
        self._admit_seq = 0
        self._seq_of: dict = {}           # slot → admission sequence no.

        if cache == "paged":
            if not model.supports_paged:
                raise ValueError(
                    f"arch {model.cfg.family!r} does not support the paged "
                    f"KV cache")
            ps = page_size or plan.tiles_for(None).page_size
            if max_len % ps:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of the page "
                    f"size {ps}")
            max_pages = max_len // ps
            # default pool: full residency for every slot (no preemption)
            n_pages = n_pages or 1 + batch_slots * max_pages
            self.pcfg = PagedCacheConfig(n_pages, ps, max_pages)
            self.alloc = PageAllocator(self.pcfg)
            self.table = BlockTable(batch_slots, self.pcfg)
            with self.mesh:
                self.serve_step_fn = plan.jit_serve_step_paged(
                    batch_slots, n_pages, ps, max_pages, donate=False)
                specs = plan.paged_state_specs(batch_slots, n_pages, ps,
                                               max_pages)
                shapes = model.paged_state_shapes(batch_slots, n_pages, ps,
                                                  max_pages)
                shardings = jax.tree.map(
                    lambda s: jax.NamedSharding(self.mesh, s), specs,
                    is_leaf=_is_spec)
                self.pools = jax.tree.map(
                    lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
                    shapes["pools"], shardings["pools"])
        else:
            with self.mesh:
                self.serve_step_fn = plan.jit_serve_step(batch_slots, max_len,
                                                         donate=False)
                specs = plan.state_specs(batch_slots, max_len)
                self.state_shardings = jax.tree.map(
                    lambda s: jax.NamedSharding(self.mesh, s), specs,
                    is_leaf=_is_spec)
                self.state = jax.tree.map(
                    lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
                    model.decode_state_shapes(batch_slots, max_len),
                    self.state_shardings)

    # --- bucketed prefill (jit cache: one entry per pow2 bucket) ---
    @property
    def prefill_cache_size(self) -> int:
        return len(self._prefill_fns)

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            gb = 0 if self.cache == "paged" else self.max_len - bucket
            model, rules = self.model, self.plan.rules

            def prefill(params, tokens, last_idx, gen_budget=gb):
                with use_rules(rules):
                    return model.prefill(params, {"tokens": tokens},
                                         gen_budget=gen_budget,
                                         last_idx=last_idx)

            fn = self._prefill_fns[bucket] = jax.jit(prefill)
        return fn

    def _run_prefill(self, params, prompt: np.ndarray):
        S = len(prompt)
        bucket = prompt_bucket(S, self.max_len)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :S] = prompt
        last_idx = jnp.asarray([S - 1], jnp.int32)
        with self.mesh:
            return self._prefill_fn(bucket)(params, jnp.asarray(tokens),
                                            last_idx)

    # --- admission ---
    def can_admit(self, req: Request) -> bool:
        """Admission control: slot capacity is checked by the caller via
        :meth:`free_slot`; paged mode additionally requires the prompt's
        pages *now* and bounds the sequence by the block-table width."""
        S = len(req.prompt)
        if S + req.max_new > self.max_len:
            return False
        if self.cache == "paged":
            return self.alloc.can_alloc(self.pcfg.pages_for(S))
        return True

    def admit(self, params, req: Request, slot: int) -> None:
        """Prefill ``req`` into ``slot``.  A request that finishes at
        admission (EOS from prefill, or a one-token budget) is marked
        ``done`` and never occupies the slot — the caller collects it."""
        S = len(req.prompt)
        logits, st = self._run_prefill(params, np.asarray(req.prompt))
        logits_np = np.asarray(logits[0, :self.model.cfg.vocab])
        if self.record_logits:
            req.first_logits = logits_np
        tok = int(logits_np.argmax())
        req.out_tokens.append(tok)
        if tok == self.eos or len(req.out_tokens) >= req.max_new:
            req.done = True
            return
        if self.cache == "paged":
            pages = self.alloc.alloc(slot, self.pcfg.pages_for(S))
            self._write_prompt_pages(st["cache"], pages)
            self.table.assign(slot, pages, pos=S)
        else:
            with self.mesh:
                self.state = jax.device_put(
                    _write_slot(self.state, st, slot,
                                self.model.state_axes()),
                    self.state_shardings)
        self.tokens = self.tokens.at[slot].set(tok)
        self.slots[slot] = req
        self._seq_of[slot] = self._admit_seq
        self._admit_seq += 1

    def _write_prompt_pages(self, cache, pages: list) -> None:
        """Scatter a batch-1 prefill KV cache into freshly allocated pages.

        ``.set`` overwrites whole pages, so this is also what *zeroes* them
        (prefill zeroed rows past ``last_idx``) — stale contents from a
        previous owner can never leak into the new sequence.
        """
        ps = self.pcfg.page_size
        rows = len(pages) * ps
        idx = jnp.asarray(pages)
        for name, kv in cache.items():
            for key in ("k", "v"):
                a = kv[key][:, 0]                    # (L, bucket, K, D)
                if a.shape[1] < rows:
                    a = jnp.pad(a, ((0, 0), (0, rows - a.shape[1]),
                                    (0, 0), (0, 0)))
                else:
                    a = a[:, :rows]
                a = a.reshape(a.shape[0], len(pages), ps, *a.shape[2:])
                a = jnp.swapaxes(a, 2, 3)            # pools are head-major
                pool = self.pools[name][key]
                self.pools[name][key] = pool.at[:, idx].set(
                    a.astype(pool.dtype))

    def _zero_pages(self, pages: list) -> None:
        idx = jnp.asarray(pages)
        for name in self.pools:
            for key in ("k", "v"):
                p = self.pools[name][key]
                self.pools[name][key] = p.at[:, idx].set(0)

    # --- paged bookkeeping ---
    def _preempt_victim(self, needy_slot: int) -> None:
        """Free the youngest-admitted active slot's pages; its request
        restarts from scratch via :attr:`requeued`."""
        candidates = [b for b, r in enumerate(self.slots)
                      if r is not None and b != needy_slot]
        victim = (max(candidates, key=lambda b: self._seq_of[b])
                  if candidates else needy_slot)
        req = self.slots[victim]
        req.out_tokens = []
        req.done = False
        req.preemptions += 1
        self.alloc.free_slot(victim)
        self.table.clear(victim)
        self.slots[victim] = None
        self._seq_of.pop(victim, None)
        self.requeued.append(req)

    def _grow_tables(self) -> None:
        """Append a page to every active slot whose next write would land
        on an unallocated (trash) page, preempting on exhaustion."""
        for b, req in enumerate(self.slots):
            if req is None or not self.table.needs_page(b):
                continue
            while not self.alloc.can_alloc(1):
                self._preempt_victim(b)
                if self.slots[b] is None:      # preempted ourselves
                    break
            if self.slots[b] is None:
                continue
            page = self.alloc.alloc(b, 1)[0]
            self._zero_pages([page])
            self.table.append_page(b, page)

    # --- decode ---
    def step(self, params) -> list:
        """Advance every active slot one token; returns the requests that
        finished this step.

        Finished requests must be *returned*, not just freed: the slot is
        recycled in the same pass (``self.slots[b] = None``), so a caller
        scanning ``server.slots`` afterwards can never observe a done
        request — the pre-fix driver collected exactly that way and its
        ``done`` list stayed empty forever.
        """
        if self.cache == "paged":
            self._grow_tables()
            state = {"pools": self.pools,
                     "block_table": jnp.asarray(self.table.table),
                     "pos": jnp.asarray(self.table.pos)}
            with self.mesh:
                logits, state = self.serve_step_fn(params, self.tokens,
                                                   state)
            self.pools = state["pools"]
        else:
            with self.mesh:
                logits, self.state = self.serve_step_fn(params, self.tokens,
                                                        self.state)
        vocab = self.model.cfg.vocab
        # ONE host sync for the whole batch (was: one int() per slot)
        nxt = np.asarray(jnp.argmax(logits[:, :vocab], axis=-1))
        if self.record_logits:
            self.last_logits = np.asarray(logits[:, :vocab])
        self.tokens = jnp.asarray(nxt.astype(np.int32))
        self.steps += 1
        finished = []
        for b, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            if self.cache == "paged":
                self.table.pos[b] += 1
            tok = int(nxt[b])
            req.out_tokens.append(tok)
            if tok == self.eos or len(req.out_tokens) >= req.max_new:
                req.done = True
                self.slots[b] = None          # recycle the slot …
                self._seq_of.pop(b, None)
                if self.cache == "paged":
                    self.alloc.free_slot(b)
                    self.table.clear(b)
                finished.append(req)          # … but hand the request back
        return finished

    def free_slot(self) -> int | None:
        for b, s in enumerate(self.slots):
            if s is None:
                return b
        return None

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    def take_requeued(self) -> list:
        out, self.requeued = self.requeued, []
        return out


def _is_spec(t) -> bool:
    return isinstance(t, jax.sharding.PartitionSpec)


def _write_slot(state, st_one, slot: int, axes) -> dict:
    """Write a batch-1 prefill state into slot ``slot`` of the batch state."""
    def one(big, small, names):
        names = tuple(names)
        if "batch" not in names:
            return big
        b_ax = names.index("batch")
        idx = [0] * big.ndim
        idx[b_ax] = slot
        sl = small
        if small.shape[b_ax] != 1:
            sl = jnp.expand_dims(small, b_ax)
        # pad/crop the kv_seq dim to the slot buffer
        for d, nm in enumerate(names):
            if nm == "kv_seq" and sl.shape[d] != big.shape[d]:
                pad = big.shape[d] - sl.shape[d]
                if pad > 0:
                    cfgpad = [(0, 0)] * sl.ndim
                    cfgpad[d] = (0, pad)
                    sl = jnp.pad(sl, cfgpad)
                else:
                    sl = jax.lax.slice_in_dim(sl, 0, big.shape[d], axis=d)
        return jax.lax.dynamic_update_slice(big, sl.astype(big.dtype), idx)

    is_axes = lambda t: isinstance(t, tuple) and all(
        isinstance(e, (str, type(None))) for e in t)
    cache = jax.tree.map(one, state["cache"], st_one["cache"], axes["cache"],
                         is_leaf=is_axes)
    return {"cache": cache,
            "pos": state["pos"].at[slot].set(st_one["pos"][0])}
