"""Device plane of the serving engine: state arrays + the fused step.

``DeviceState`` owns every array the decode loop touches on device —
the sampled-token chain, per-slot lengths, block table, active mask,
allocated-page counts, the prefill first-token buffer and the sampling
RNG key — and exposes ONE jitted transition per engine step.  Slot
admission, page-table growth, teacher-forced token overrides, slot
resets, the decode itself and the sampler are all folded into that
single dispatch (``stats()["dispatches_per_step"] == 1``), replacing the
four separate ``_admit``/``_grow``/``_tf``/``_reset`` scatters of the
PR 1 hot path.

Page-growth ALLOCATION is decided device-side: the fused step computes
the per-slot need mask from the device-resident lengths
(``lengths // block + 1 > pages``) and consumes host-supplied candidate
page ids for exactly the slots the mask selects (per-slot pools
degenerate the shared-buffer prefix-sum to a per-slot candidate; the
prefix-sum over the need mask still yields the allocation count).  The
host never reads device lengths — it advances a deterministic mirror
(+1 per active slot per step) that provably agrees with the device
computation, and uses it only to pop the same free-list heads for pool
bookkeeping and to detect exhaustion (back-pressure) BEFORE dispatch.

Sampling runs on device inside the same dispatch: temperature/top-p via
sorted inverse-CDF (:func:`sample_tokens`), with greedy argmax as the
statically-compiled ``temperature == 0`` fast path.  The uniforms are
COUNTER-BASED (:func:`counter_uniform`): each slot carries its request's
``sample_key`` and the u for the token at sequence index ``pos`` is a
pure function of ``(sample_key, pos)`` — no engine-resident RNG chain —
so a sampled continuation is bit-reproducible on any replica that knows
the prefix and the key (lifecycle replay, tier-plane KV handoff).
``repro.serving.sampling`` holds the host reference implementation;
tests assert parity.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import to_pages


def sample_tokens(logits, u, temperature: float, top_p: float):
    """Temperature/top-p sampling via sorted inverse CDF (pure jnp).

    Deterministic given ``u`` (B,) uniforms — mirrored bit-for-bit-modulo
    -float-associativity by ``repro.serving.sampling.sample_ref``, which
    tests assert against.
    """
    lf = logits.astype(jnp.float32) / temperature
    order = jnp.argsort(-lf, axis=-1)  # descending, stable
    probs = jax.nn.softmax(jnp.take_along_axis(lf, order, axis=-1), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # nucleus: smallest prefix with cumulative mass >= top_p
    keep = (cum - probs) < top_p
    kept = jnp.where(keep, probs, 0.0)
    kept = kept / kept.sum(axis=-1, keepdims=True)
    kcum = jnp.cumsum(kept, axis=-1)
    last = keep.sum(axis=-1).astype(jnp.int32) - 1
    idx = jnp.minimum(
        jnp.sum((kcum <= u[:, None]).astype(jnp.int32), axis=-1), last
    )
    return jnp.take_along_axis(order, idx[:, None], axis=-1)[:, 0].astype(
        jnp.int32
    )


def counter_uniform(seed, position):
    """The serving stack's sampling uniform: a pure counter-based function
    of ``(sample_key, token position)`` — NO engine-resident RNG chain.

    ``u = uniform(fold_in(fold_in(PRNGKey(0), seed), position))``, so the
    u that samples the token at sequence index ``position`` depends only
    on the request's journaled ``sample_key`` and the index itself.  Any
    replica that knows the prefix and the key reproduces the continuation
    bit-for-bit — the property the lifecycle plane's sampled replay and
    the tier plane's mid-request KV handoff both rest on.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    key = jax.random.fold_in(key, position)
    return jax.random.uniform(key, (), jnp.float32)


class DeviceState:
    """Device-resident serving state with a single fused step transition.

    Host-side events (admission, prefill chunks, finish, teacher-forcing)
    are *staged* into pending buffers and applied INSIDE the next fused
    dispatch, in order: reset -> prefill-chunk -> admit -> teacher-force
    -> grow -> decode -> sample.  The chunk lane runs BEFORE the admit
    lane so a prompt's final chunk can write the first token into
    ``first_buf`` and the admit staged for the same dispatch can consume
    it — admission steps stay one dispatch.
    """

    def __init__(
        self,
        model,
        params,
        cache,
        *,
        max_slots: int,
        mb: int,
        block: int,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        chunk_tokens: int = 0,
        global_pages: bool = False,
        speculate_k: int = 0,
        draft_layers: int = 0,
        device: Optional[jax.Device] = None,
    ) -> None:
        self.model = model
        self.params = params
        self.cache = cache
        self.max_slots = max_slots
        self.mb = mb
        self.block = block
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        # global page ids (gid = slot * n_pool + page): block-table rows
        # address the slot-flattened pool, so a row may reference pages
        # OWNED BY OTHER SLOTS — the device substrate of CoW forking
        self.global_pages = bool(global_pages)
        # speculative-decode lane: k > 0 folds draft-and-verify into the
        # SAME fused dispatch (greedy only; the engine asserts).  The
        # draft model is the first `draft_layers` of the target, sharing
        # its embedding/unembedding and reading the SAME paged KV; its
        # own KV writes land in a sliced cache copy that is discarded —
        # the verify pass rewrites the same positions with identical
        # values into the real cache.
        self.speculate_k = int(speculate_k)
        self.draft_layers = int(draft_layers)
        assert self.speculate_k < block, (
            "speculate_k must stay below the page size so device growth "
            "is at most one page per slot per dispatch"
        )
        # chunked-prefill lane width (0 = lane disabled / legacy prefill).
        # ONE static shape for the whole engine lifetime: the fused step
        # compiles a with-chunk variant per n_kv bucket, never a new
        # entry per prompt length (chunk_shapes observes this).
        self.chunk_tokens = int(chunk_tokens)
        self.chunk_shapes: set = set()

        B = max_slots
        # step operands live on the engine's device, beside the committed
        # params and cache (None: the default device)
        with jax.default_device(device):
            self.tokens = jnp.zeros((B, 1), jnp.int32)
            self.lengths = jnp.zeros((B,), jnp.int32)
            self.table = jnp.zeros((B, mb), jnp.int32)
            self.mask = jnp.zeros((B,), jnp.int32)
            self.pages = jnp.zeros((B,), jnp.int32)
            self.first_buf = jnp.zeros((B,), jnp.int32)
            # per-slot sample keys (installed at admit): sampling is a
            # pure function of (key, position) — see counter_uniform — so
            # a request's stream is replica-independent.  The legacy
            # engine rng chain is gone; `seed` survives only as the
            # engine-level default key derivation salt (see
            # ServingEngine.submit).
            self.seeds = jnp.zeros((B,), jnp.int32)
            # shared all-zeros operands for the steady state (no events
            # pending) — device-resident so the common dispatch passes
            # already-committed buffers instead of re-uploading numpy
            # zeros; event paths build fresh numpy arrays (same avals,
            # same compile)
            self._zeros = jnp.zeros((B,), jnp.int32)
            self._zeros_row = jnp.zeros((B, mb), jnp.int32)
            # chunk-lane dummies (unused by the has_chunk=False variant,
            # but the jit signature is shared, so the avals must stay
            # fixed)
            self._zero = jnp.int32(0)
            self._ck_zeros_toks = jnp.zeros(
                (1, max(self.chunk_tokens, 1)), jnp.int32)
            self._ck_zeros_row = jnp.zeros((mb,), jnp.int32)
            self._ck_zeros_pages = jnp.zeros(
                (max(self.chunk_tokens // block, 1),), jnp.int32)

        # staged host events, applied by the next fused dispatch
        self._pending_resets: List[int] = []
        self._pending_admits: List[Tuple] = []
        self._pending_chunk: Optional[Tuple] = None
        self.stage_ns = 0  # host time spent building step operands

        # dispatch accounting (decode plane vs admission plane).  Any
        # device call made on behalf of a decode step MUST bump
        # decode_dispatches; the ENGINE counts the steps, so the
        # dispatches-per-step ratio catches a reintroduced extra scatter.
        self.decode_dispatches = 0
        self.admission_dispatches = 0
        self.migration_dispatches = 0  # cluster plane, cold path
        self.page_move_buckets: set = set()  # pow2 handoff index shapes

        # ---- jitted device functions ----
        # n_kv is static: one compile per power-of-two page-sweep bucket
        # (x2 with the chunked-prefill lane folded in — has_chunk is the
        # ONLY other static axis; the chunk lane's token shape is fixed at
        # construction, so prompt length never mints a compile entry).
        # Donated: cache, lengths, table, mask, pages, seeds.  NOT
        # donated: tokens (in-flight pipeline entries keep references for
        # their completion device_get) and first_buf (returned updated
        # instead — the chunk lane writes it on a prompt's final chunk).
        self._step = jax.jit(
            self._step_fn, donate_argnums=(1, 3, 4, 5, 6, 8),
            static_argnums=(29, 30),
        )
        # fused prefill+KV-load, keyed by bucketed seq length: a classic
        # admission is ONE dispatch (satellite of the PR 2 open item)
        self._prefill_cache: Dict[int, Any] = {}
        self._copier = jax.jit(self._copy_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # fused step (ONE dispatch per engine step)
    # ------------------------------------------------------------------
    def _step_fn(self, params, cache, tokens, lengths, table, mask, pages,
                 first_buf, seeds, reset_m, admit_m, admit_len, admit_row,
                 admit_pages, admit_tok, admit_from_buf, admit_set_tok,
                 admit_seed, tf_m, tf_vals, cand_pages, ck_tokens, ck_slot,
                 ck_start, ck_row, ck_pages, ck_last, ck_last_index,
                 ck_seed, n_kv, has_chunk):
        B = self.max_slots
        rows = jnp.arange(B, dtype=jnp.int32)

        # 1. slot resets (requests finished since the last dispatch)
        keep = 1 - reset_m
        lengths = lengths * keep
        mask = mask * keep
        pages = pages * keep
        seeds = seeds * keep
        table = table * keep[:, None]

        # 1b. chunked-prefill lane (at most ONE chunk per step; static
        # branch, so decode-only steps compile without it).  The chunk's
        # KV lands in the admitting slot's pool pages; on the prompt's
        # final chunk the first token is sampled HERE and dropped into
        # first_buf, which the admit lane (staged for this same dispatch)
        # consumes below — prompt-done -> emit-token-1 is a pure
        # device-side transition, still one dispatch.
        chunk_first = self._zero
        if has_chunk:
            ck_logits, cache = self.model.prefill_chunk(
                params, cache,
                {"tokens": ck_tokens, "start": ck_start, "slot": ck_slot,
                 "row": ck_row, "pages": ck_pages,
                 "last_index": ck_last_index},
                n_kv=n_kv, global_pages=self.global_pages,
            )
            if self.temperature > 0.0:
                # token 1 lands at sequence index start + last_index + 1
                # (== the prompt length, on the final chunk)
                u = counter_uniform(ck_seed,
                                    ck_start + ck_last_index + 1)[None]
                first = sample_tokens(ck_logits, u, self.temperature,
                                      self.top_p)
            else:
                first = jnp.argmax(ck_logits, axis=-1).astype(jnp.int32)
            chunk_first = first[0]
            first_buf = jnp.where(ck_last == 1,
                                  first_buf.at[ck_slot].set(chunk_first),
                                  first_buf)

        # 2. admissions
        lengths = jnp.where(admit_m == 1, admit_len, lengths)
        table = jnp.where(admit_m[:, None] == 1, admit_row, table)
        mask = jnp.maximum(mask, admit_m)
        pages = jnp.where(admit_m == 1, admit_pages, pages)
        seeds = jnp.where(admit_m == 1, admit_seed, seeds)
        first = jnp.where(admit_from_buf == 1, first_buf, admit_tok)
        tokens = jnp.where(admit_set_tok[:, None] == 1, first[:, None],
                           tokens)

        # 3. teacher-forced suffix overrides (prefix-cache replay)
        tokens = jnp.where(tf_m[:, None] == 1, tf_vals[:, None], tokens)

        # 4. device-side page growth: the need mask comes from the
        # DEVICE lengths; the host only supplied per-slot candidates.
        # The speculative lane writes up to `speculate_k` positions past
        # `lengths` this dispatch, so the horizon extends by k — still at
        # most ONE page per slot per dispatch because k < block.
        look = self.speculate_k
        need = ((mask == 1)
                & (((lengths + look) // self.block + 1) > pages)
                & (pages < self.mb))
        pos = jnp.clip(pages, 0, self.mb - 1)
        cur = table[rows, pos]
        table = table.at[rows, pos].set(jnp.where(need, cand_pages, cur))
        pages = pages + need.astype(jnp.int32)

        gp = self.global_pages
        if self.speculate_k > 0:
            # 5s. speculative draft-and-verify, ONE dispatch (greedy).
            # Draft: k sequential early-exit steps over the first
            # `draft_layers` layers (sliced params + sliced cache copy;
            # the copy is discarded — verify rewrites identical KV).
            k = self.speculate_k
            dl = self.draft_layers
            dparams = dict(params, layers=jax.tree.map(
                lambda a: a[:dl], params["layers"]))
            dcache = dict(cache, layers=jax.tree.map(
                lambda a: a[:dl], cache["layers"]))
            d_tok, d_len, drafts = tokens, lengths, []
            for _ in range(k):
                d_logits, dcache = self.model.decode_step(
                    dparams, dcache,
                    {"tokens": d_tok, "lengths": d_len,
                     "block_table": table},
                    n_kv=n_kv, global_pages=gp,
                )
                nxt = jnp.argmax(d_logits, axis=-1).astype(jnp.int32)
                drafts.append(nxt)
                d_tok = nxt[:, None]
                d_len = d_len + 1
            # Verify: k+1 full steps teacher-forcing [t0, d1..dk] into
            # the REAL cache; v_i is the target model's token for
            # position lengths+i+1 — bit-identical to what non-
            # speculative greedy decode would produce there.
            v_tok, v_len, v_list = tokens, lengths, []
            for i in range(k + 1):
                logits, cache = self.model.decode_step(
                    params, cache,
                    {"tokens": v_tok, "lengths": v_len,
                     "block_table": table},
                    n_kv=n_kv, global_pages=gp,
                )
                v_list.append(
                    jnp.argmax(logits, axis=-1).astype(jnp.int32))
                if i < k:
                    v_tok = drafts[i][:, None]
                    v_len = v_len + 1
            v = jnp.stack(v_list, axis=1)   # (B, k+1)
            d = jnp.stack(drafts, axis=1)   # (B, k)
            # accept the longest prefix of drafts the target agrees with;
            # counts = accepted + 1 (the verify chain's bonus token).
            # Slots mid teacher-forcing advance exactly 1 like a plain
            # step (their "drafts" are junk — the forced token overrides).
            acc = jnp.cumprod((d == v[:, :k]).astype(jnp.int32), axis=1)
            spec_m = (mask == 1) & (tf_m == 0)
            counts = jnp.where(spec_m, acc.sum(axis=1) + 1, 1)
            new_tokens = jnp.take_along_axis(
                v, counts[:, None] - 1, axis=1)[:, 0]
            # rejected drafts' KV (positions lengths+counts..lengths+k)
            # stays garbage but is never attended: lengths advance by
            # counts, and later steps overwrite those offsets before any
            # window reaches them.
            return (new_tokens[:, None], cache, lengths + counts * mask,
                    table, mask, pages, first_buf, seeds, chunk_first,
                    v, counts * mask)

        # 5. decode
        logits, cache = self.model.decode_step(
            params, cache,
            {"tokens": tokens, "lengths": lengths, "block_table": table},
            n_kv=n_kv, global_pages=gp,
        )

        # 6. sample (greedy is the statically-compiled temperature=0 path).
        # The token this dispatch emits lands at sequence index
        # lengths + 1 (index `lengths` holds the token being consumed),
        # so its uniform is counter_uniform(slot key, lengths + 1) —
        # position-keyed, engine-independent.
        if self.temperature > 0.0:
            u = jax.vmap(counter_uniform)(seeds, lengths + 1)
            new_tokens = sample_tokens(logits, u, self.temperature,
                                       self.top_p)
        else:
            new_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (new_tokens[:, None], cache, lengths + mask, table, mask,
                pages, first_buf, seeds, chunk_first)

    # ------------------------------------------------------------------
    # admission-plane bodies (per-request, not per-step)
    # ------------------------------------------------------------------
    def _prefill_fn(self, params, cache, tokens, last_index, first_buf,
                    seed, slot, pages):
        """Fused prefill: forward pass + first-token sample + KV scatter
        into this slot's pages, in ONE dispatch.  ``pages`` always spans
        the full power-of-two bucket (the caller pads spare entries with
        the scratch page 0), so the compile cache stays keyed on the
        bucketed seq length alone — O(log(max_seq/block)) entries."""
        logits, kv = self.model.prefill(
            params, {"tokens": tokens, "last_index": last_index}
        )
        # sample on-device: the host never syncs on prefill logits; the
        # first token lands in first_buf for the next fused step AND is
        # returned as a scalar for the pipeline-lagged completion read.
        # Token 1 uses the SAME sampler as decode steps, so sampled mode
        # is consistent from position 0.
        if self.temperature > 0.0:
            # token 1's sequence index is last_index + 1 == prompt length
            u = counter_uniform(seed, last_index[0] + 1)[None]
            first = sample_tokens(logits, u, self.temperature, self.top_p)
        else:
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # the whole bucket scatters; spare blocks land on scratch page 0
        kp = cache["layers"]["k_pool"]
        kr = to_pages(kv["k"][:, 0], self.block)  # (L, nb, Hkv, block, D)
        vr = to_pages(kv["v"][:, 0], self.block)
        kp = kp.at[:, slot, pages].set(kr.astype(kp.dtype))
        vp = cache["layers"]["v_pool"].at[:, slot, pages].set(
            vr.astype(kp.dtype)
        )
        cache = dict(cache, layers=dict(
            cache["layers"], k_pool=kp, v_pool=vp))
        return cache, first_buf.at[slot].set(first[0]), first[0]

    def _copy_fn(self, cache, src_slots, src_pages, dst_slot, dst_pages):
        kp = cache["layers"]["k_pool"]
        vp = cache["layers"]["v_pool"]
        kp = kp.at[:, dst_slot, dst_pages].set(kp[:, src_slots, src_pages])
        vp = vp.at[:, dst_slot, dst_pages].set(vp[:, src_slots, src_pages])
        return dict(cache, layers=dict(cache["layers"], k_pool=kp,
                                       v_pool=vp))

    # ------------------------------------------------------------------
    # staging API (host events -> next fused dispatch)
    # ------------------------------------------------------------------
    def stage_reset(self, slot: int) -> None:
        self._pending_resets.append(slot)

    def stage_admit(self, slot: int, length: int, row: np.ndarray,
                    n_pages: int, *, token: int = 0,
                    token_from_buf: bool = False,
                    set_token: bool = False, seed: int = 0) -> None:
        self._pending_admits.append(
            (slot, length, row, n_pages, token, token_from_buf, set_token,
             seed)
        )

    def has_pending_chunk(self) -> bool:
        """True when a prefill chunk is staged for the next dispatch
        (the engine must dispatch even with no active decode slots)."""
        return self._pending_chunk is not None

    def prefill_jit_shapes(self) -> list:
        """Compiled legacy whole-prompt prefill shapes (pow2 buckets);
        empty for chunked engines — the compile-cache-collapse
        observable."""
        return sorted(self._prefill_cache)

    def fused_step_compiles(self) -> int:
        """Fused-step jit signature-cache entries (-1 if the runtime has
        no introspection).  CAVEAT: this over-counts XLA programs — the
        cache also keys on operand-placement combinations (numpy event
        operands vs device-resident steady-state zeros), so it bounds
        but does not equal (n_kv buckets x has_chunk).  It saturates
        once every step-kind combo has run; it must NOT grow with the
        number of distinct prompt lengths (the pow2-bucket failure mode
        this PR removes)."""
        cache_size = getattr(self._step, "_cache_size", None)
        return cache_size() if cache_size is not None else -1

    def stage_chunk(self, slot: int, tokens: np.ndarray, start: int,
                    row: np.ndarray, pages: np.ndarray, is_last: bool,
                    last_index: int, seed: int = 0) -> None:
        """Stage one prefill chunk for the next fused dispatch.  At most
        one chunk rides per step (the scheduler's interleaving policy);
        ``tokens`` is always exactly ``chunk_tokens`` wide (the last chunk
        pads), so the lane holds ONE compiled shape forever."""
        assert self.chunk_tokens and len(tokens) == self.chunk_tokens
        assert self._pending_chunk is None, "one chunk per fused step"
        self.chunk_shapes.add(len(tokens))
        self._pending_chunk = (slot, tokens, start, row, pages, is_last,
                               last_index, seed)

    # ------------------------------------------------------------------
    # dispatch API
    # ------------------------------------------------------------------
    def prefill(self, tokens_np: np.ndarray, last_index: int, slot: int,
                nb: int, pages, seed: int = 0) -> Any:
        """Bucketed fused prefill + KV load: ONE dispatch per classic
        admission.  Returns the first-token device scalar.

        The scatter covers the whole bucket: blocks past the ``nb``
        allocated ones write (garbage KV of the token padding) to the
        scratch page 0, exactly like inactive-slot decode writes — so
        ``pages`` has a bucket-static shape and the jit cache is keyed
        on the bucketed seq length alone."""
        S = tokens_np.shape[1]
        if S not in self._prefill_cache:
            self._prefill_cache[S] = jax.jit(
                self._prefill_fn, donate_argnums=(1, 4),
            )
        padded = list(pages) + [0] * (S // self.block - nb)
        self.cache, self.first_buf, first = (
            self._prefill_cache[S](
                self.params, self.cache, jnp.asarray(tokens_np),
                jnp.asarray([last_index], jnp.int32), self.first_buf,
                np.int32(seed), np.int32(slot),
                jnp.asarray(padded, jnp.int32),
            )
        )
        self.admission_dispatches += 1
        return first

    # ------------------------------------------------------------------
    # cluster-plane migration primitives (cold path: replicas own
    # separate device arrays, so cross-replica moves go through the host)
    # ------------------------------------------------------------------
    def _page_move_bucket(self, n: int) -> int:
        """Pow2 bucket for page-move index vectors.  Gather/scatter
        programs are shape-keyed, so an unbucketed move compiles once
        per distinct page count — a mid-request handoff of a new length
        then stalls a whole cluster tick behind XLA.  Padding the index
        vector to a pow2 bucket caps the cache at log2(pool) programs
        per direction."""
        b = 1
        while b < n:
            b <<= 1
        self.page_move_buckets.add(b)
        return b

    def read_pages(self, slot: int, pages) -> Tuple[np.ndarray, np.ndarray]:
        """Pull one slot's pages to host: (L, n, Hkv, block, D) k/v pair.
        Synchronous by design — migration is not the hot path, and the
        caller holds a cluster hold so the pages cannot be reclaimed."""
        n = len(pages)
        nb = self._page_move_bucket(n)
        idx = jnp.asarray(list(pages) + [0] * (nb - n), jnp.int32)
        k = np.asarray(self.cache["layers"]["k_pool"][:, slot, idx])[:, :n]
        v = np.asarray(self.cache["layers"]["v_pool"][:, slot, idx])[:, :n]
        self.migration_dispatches += 1
        return k, v

    def write_pages(self, slot: int, pages, k: np.ndarray,
                    v: np.ndarray) -> None:
        """Install host KV blocks into this replica's pages.  The index
        vector is padded to the pow2 bucket with scratch page 0 (and the
        payload with zeros), so pad lanes write garbage to the scratch
        page exactly like inactive-slot decode writes."""
        n = len(pages)
        nb = self._page_move_bucket(n)
        idx = jnp.asarray(list(pages) + [0] * (nb - n), jnp.int32)
        pad = [(0, 0), (0, nb - n)] + [(0, 0)] * (k.ndim - 2)
        kp = self.cache["layers"]["k_pool"]
        vp = self.cache["layers"]["v_pool"]
        self.cache = dict(self.cache, layers=dict(
            self.cache["layers"],
            k_pool=kp.at[:, slot, idx].set(
                jnp.asarray(np.pad(k, pad), kp.dtype)),
            v_pool=vp.at[:, slot, idx].set(
                jnp.asarray(np.pad(v, pad), vp.dtype)),
        ))
        self.migration_dispatches += 1

    def copy_pages(self, src_slots, src_pages, dst_slot, dst_pages) -> None:
        self.cache = self._copier(
            self.cache,
            jnp.asarray(src_slots, jnp.int32),
            jnp.asarray(src_pages, jnp.int32),
            dst_slot,
            jnp.asarray(dst_pages, jnp.int32),
        )
        self.admission_dispatches += 1

    def dispatch(self, tf: Dict[int, int], grow: Dict[int, int],
                 n_kv: int):
        """Run ONE fused engine step; returns ``(tokens, chunk_first)`` —
        the new token chain plus the chunk lane's first-token scalar
        (meaningful only when the staged chunk was a prompt's last).

        ``tf``   — slot -> teacher-forced token for this step.
        ``grow`` — slot -> candidate page id (consumed iff the device
                   need mask selects the slot; host and device agree by
                   construction, see module docstring).
        """
        t0 = time.perf_counter_ns()
        has_chunk = self._pending_chunk is not None
        operands = self._operands(tf, grow)
        self.stage_ns += time.perf_counter_ns() - t0

        out = self._step(*operands, n_kv, has_chunk)
        spec = None
        if self.speculate_k > 0:
            (self.tokens, self.cache, self.lengths, self.table, self.mask,
             self.pages, self.first_buf, self.seeds, chunk_first, v,
             counts) = out
            spec = (v, counts)
        else:
            (self.tokens, self.cache, self.lengths, self.table, self.mask,
             self.pages, self.first_buf, self.seeds, chunk_first) = out
        self._pending_resets.clear()
        self._pending_admits.clear()
        self._pending_chunk = None
        self.decode_dispatches += 1
        return self.tokens, chunk_first, spec

    def compiled_step_text(self, n_kv: int, has_chunk: bool) -> str:
        """Compiled program text of one fused-step variant, lowered with
        the current operand avals — what the device runs (e.g. to confirm
        that a Pallas kernel is in it).  Compiles; runs nothing."""
        lowered = self._step.lower(*self._operands({}, {}), n_kv, has_chunk)
        return lowered.compile().as_text()

    def _operands(self, tf: Dict[int, int], grow: Dict[int, int]) -> tuple:
        """The fused step's positional operands for the staged events:
        fresh numpy arrays where an event is staged, the device-resident
        zeros otherwise (same avals either way)."""
        B, mb = self.max_slots, self.mb
        zeros = self._zeros
        reset_m = zeros
        if self._pending_resets:
            reset_m = np.zeros((B,), np.int32)
            for s in self._pending_resets:
                reset_m[s] = 1
        admit_m = admit_len = admit_pages = zeros
        admit_tok = admit_from_buf = admit_set_tok = admit_seed = zeros
        admit_row = self._zeros_row
        if self._pending_admits:
            admit_m = np.zeros((B,), np.int32)
            admit_len = np.zeros((B,), np.int32)
            admit_row = np.zeros((B, mb), np.int32)
            admit_pages = np.zeros((B,), np.int32)
            admit_tok = np.zeros((B,), np.int32)
            admit_from_buf = np.zeros((B,), np.int32)
            admit_set_tok = np.zeros((B,), np.int32)
            admit_seed = np.zeros((B,), np.int32)
            for (slot, length, row, n_pages, tok, from_buf, set_tok,
                 seed) in self._pending_admits:
                admit_m[slot] = 1
                admit_len[slot] = length
                admit_row[slot] = row
                admit_pages[slot] = n_pages
                admit_tok[slot] = tok
                admit_from_buf[slot] = 1 if from_buf else 0
                admit_set_tok[slot] = 1 if set_tok else 0
                admit_seed[slot] = seed
        tf_m = tf_vals = zeros
        if tf:
            tf_m = np.zeros((B,), np.int32)
            tf_vals = np.zeros((B,), np.int32)
            for slot, tok in tf.items():
                tf_m[slot] = 1
                tf_vals[slot] = tok
        cand = zeros
        if grow:
            cand = np.zeros((B,), np.int32)
            for slot, page in grow.items():
                cand[slot] = page
        ck_tokens = self._ck_zeros_toks
        ck_slot = ck_start = ck_last = ck_last_index = self._zero
        ck_seed = self._zero
        ck_row = self._ck_zeros_row
        ck_pages = self._ck_zeros_pages
        if self._pending_chunk is not None:
            (c_slot, c_toks, c_start, c_row, c_pages, c_is_last,
             c_last_index, c_seed) = self._pending_chunk
            ck_tokens = np.asarray(c_toks, np.int32)[None]
            ck_slot = np.int32(c_slot)
            ck_start = np.int32(c_start)
            ck_row = np.asarray(c_row, np.int32)
            ck_pages = np.asarray(c_pages, np.int32)
            ck_last = np.int32(1 if c_is_last else 0)
            ck_last_index = np.int32(c_last_index)
            ck_seed = np.int32(c_seed)
        return (self.params, self.cache, self.tokens, self.lengths,
                self.table, self.mask, self.pages, self.first_buf,
                self.seeds, reset_m, admit_m, admit_len, admit_row,
                admit_pages, admit_tok, admit_from_buf, admit_set_tok,
                admit_seed, tf_m, tf_vals, cand, ck_tokens, ck_slot,
                ck_start, ck_row, ck_pages, ck_last, ck_last_index,
                ck_seed)
