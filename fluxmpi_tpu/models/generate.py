"""Autoregressive generation with a KV cache for :class:`TransformerLM`.

Inference surface beyond the reference's training-only scope (its models
are user-land Flux code; no generation utilities exist to mirror) — a
"complete framework" extra, built the TPU way: ONE ``lax.scan`` drives
prefill and generation (prompt positions teacher-force the next token,
generated positions sample), every step extends the flax attention KV
caches in place, shapes are fully static, and the whole loop jits into a
single program — no per-token host round trip.

The decode pass runs the plain dense single-query attend (optimal for
one query against a cached K/V; the flash/ring ``attention_fn`` kernels
are training-time constructs and are bypassed, see
``EncoderBlock.__call__``). Parameter trees are identical between the
training and decode configurations, so trained checkpoints load
directly.

Prefill: the prompt populates the KV caches through ONE batched causal
forward (:func:`prefill_kv` / :func:`prefill_cache` — the train-mode
model runs over the whole prompt, the per-layer pre-attention
LayerNorm outputs are captured, and the K/V projections are applied
outside the module and written into the flax cache in one pass), so
time-to-first-token is O(1) forwards instead of O(prompt_len)
sequential scan ticks. ``generate(prefill="scan")`` keeps the original
one-token-per-tick prefill (the whole loop stays a single compiled
program); the two paths are bit-for-bit equivalence-tested for greedy
decoding, the default ``"auto"`` only takes the batched path for
models that declare it token-exact (``batched_prefill_safe`` — MoE
capacity routing keeps the scan, see the MoE note below), and the
batched kernel is also what the serving plane's prefill phase calls
(:mod:`fluxmpi_tpu.serving`).

MoE note: capacity-based routing can DROP over-capacity tokens in a
batched forward that single-token decode never drops, so an MoE LM's
decode continuations can legitimately differ from a full-recompute
argmax loop unless capacity is ample (see
``tests/test_moe.py::test_moe_lm_generates``).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

__all__ = ["generate", "beam_search", "prefill_kv", "prefill_cache"]


def _decode_twin(model):
    """The same LM configured for cached single-position decoding —
    identical parameter tree (``decode``/``attention_fn``/``dropout``
    affect computation, not parameters)."""
    return model.clone(decode=True, attention_fn=None, dropout=0.0)


def _validate_lengths(model, plen: int, max_new_tokens: int) -> int:
    """Shared prompt/continuation length checks; returns total length."""
    total = plen + int(max_new_tokens)
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if total > model.max_len:
        raise ValueError(
            f"prompt_len + max_new_tokens = {total} exceeds the model's "
            f"max_len {model.max_len}"
        )
    return total


def _validate_eos(model, eos_token: int | None) -> None:
    """An out-of-range eos can never be emitted (and its scatter into
    the absorption row is silently dropped) — surface the argument
    mistake instead of letting it look like a model problem."""
    if eos_token is not None and not 0 <= eos_token < model.vocab_size:
        raise ValueError(
            f"eos_token {eos_token} is outside the model's vocabulary "
            f"[0, {model.vocab_size})"
        )


def _sized_cache(twin, rows: int, total: int):
    """Zero KV caches sized for ``rows`` sequences of length ``total``.

    flax's decode caches initialize to zeros (keys, values, index), so
    building them from ``eval_shape`` alone is exact and skips the full
    wasted forward pass a real init would run."""
    shapes = jax.eval_shape(
        lambda: twin.init(
            jax.random.PRNGKey(0), jnp.zeros((rows, total), jnp.int32),
            train=False,
        )["cache"]
    )
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )


_BLOCK_RE = re.compile(r"block_(\d+)$")


def layer_index(path) -> int:
    """Encoder-layer index of a cache/params tree path (the ``block_<i>``
    component of :class:`TransformerLM`'s module tree). Shared by the
    batched prefill below and the serving plane's block-cache
    gather/scatter, which both need a stable layer ordering that survives
    ``block_10`` sorting after ``block_2``."""
    for entry in path:
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            m = _BLOCK_RE.match(key)
            if m:
                return int(m.group(1))
    raise ValueError(
        f"no block_<i> component in cache path {jax.tree_util.keystr(path)!r}"
        " — the model's encoder layers are not TransformerLM-shaped"
    )


def _is_ln1(path) -> bool:
    keys = [getattr(e, "key", None) for e in path]
    return "ln1" in keys


def prefill_kv(model, params, tokens: jnp.ndarray, head_at=None):
    """K/V projections for every prompt position from ONE batched causal
    forward — the O(1)-forwards prefill kernel.

    Runs the TRAINING-configuration model (causal mask, no cache) over
    ``tokens`` ``[batch, plen]``, capturing each block's pre-attention
    LayerNorm (``ln1``) output, and applies the attention ``key`` /
    ``value`` projections outside the module — exactly the tensors
    flax's decode cache banks per position, computed for all positions
    at once. Right-padding is safe: the causal mask keeps positions
    ``< plen_r`` of a row independent of anything after them, so callers
    with ragged prompts pad, prefill, and discard the tail.

    Returns ``(k, v, logits)``: ``k``/``v`` are
    ``[num_layers, batch, plen, num_heads, head_dim]`` in cache layer
    order (:func:`layer_index`), ``logits`` is the full-sequence
    ``[batch, plen, vocab]`` (position ``plen - 1`` is the
    next-token distribution after the whole prompt). With ``head_at``
    (``[batch]`` positions) the head runs at that one position a row and
    ``logits`` is ``[batch, vocab]``: a padded prompt's other rows of
    logits are never built.
    """
    fwd = model.clone(decode=False, attention_fn=None, dropout=0.0)
    logits, state = fwd.apply(
        {"params": params["params"]},
        tokens.astype(jnp.int32),
        train=False,
        hidden=head_at is not None,
        capture_intermediates=lambda mdl, _: mdl.name == "ln1",
        mutable=["intermediates"],
    )
    if head_at is not None:
        # The tied head as ``nn.Embed.attend`` computes it, on one row.
        x, embedding = logits
        x = jnp.take_along_axis(
            x, jnp.asarray(head_at)[:, None, None], axis=1
        )[:, 0]
        logits = jnp.dot(x.astype(model.dtype),
                         embedding.astype(model.dtype).T)
    flat_h = [
        (layer_index(path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            state["intermediates"]
        )[0]
        if _is_ln1(path)
    ]
    flat_h.sort(key=lambda t: t[0])
    proj: dict[int, dict[str, dict[str, jnp.ndarray]]] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        params["params"]
    )[0]:
        keys = [getattr(e, "key", None) for e in path]
        if "attn" in keys and keys[-2] in ("key", "value"):
            proj.setdefault(layer_index(path), {}).setdefault(
                keys[-2], {}
            )[keys[-1]] = leaf
    if len(flat_h) != len(proj):
        raise ValueError(
            f"captured {len(flat_h)} ln1 outputs but found attention "
            f"projections for {len(proj)} layers — the model is not "
            f"TransformerLM-shaped"
        )
    dtype = model.dtype
    ks, vs = [], []
    for idx, h in flat_h:
        h = h.astype(dtype)
        layer = proj[idx]
        for which, out in (("key", ks), ("value", vs)):
            p = layer[which]
            # The same contraction DenseGeneral performs (kernel
            # [d_model, heads, head_dim], promoted to the module dtype).
            y = jnp.einsum("bld,dhn->blhn", h, p["kernel"].astype(dtype))
            if "bias" in p:
                y = y + p["bias"].astype(dtype)
            out.append(y)
    return jnp.stack(ks), jnp.stack(vs), logits


def cache_template(twin, rows: int, total: int):
    """Shape/dtype skeleton of the decode twin's flax cache for ``rows``
    sequences of length ``total`` (eval_shape only — no forward pass)."""
    return jax.eval_shape(
        lambda: twin.init(
            jax.random.PRNGKey(0), jnp.zeros((rows, total), jnp.int32),
            train=False,
        )["cache"]
    )


def prefill_cache(model, params, prompt: jnp.ndarray, total: int):
    """Batched prefill into a fresh flax decode cache.

    One causal forward (:func:`prefill_kv`) writes the prompt's K/V into
    a cache sized for ``total`` positions, with every layer's
    ``cache_index`` advanced past the prompt — the state the
    one-token-per-tick scan would reach after ``plen`` ticks, in one
    pass. Returns ``(cache, last_logits)`` where ``last_logits``
    ``[batch, vocab]`` is the next-token distribution after the prompt.
    """
    b, plen = prompt.shape
    twin = _decode_twin(model)
    k, v, logits = prefill_kv(model, params, prompt)
    tmpl = cache_template(twin, b, total)

    def fill(path, leaf):
        name = path[-1].key
        if name == "cached_key":
            z = jnp.zeros(leaf.shape, leaf.dtype)
            return z.at[:, :plen].set(k[layer_index(path)].astype(leaf.dtype))
        if name == "cached_value":
            z = jnp.zeros(leaf.shape, leaf.dtype)
            return z.at[:, :plen].set(v[layer_index(path)].astype(leaf.dtype))
        if name == "cache_index":
            return jnp.asarray(plen, leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    cache = jax.tree_util.tree_map_with_path(fill, tmpl)
    return cache, logits[:, plen - 1]


def generate(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_token: int | None = None,
    rng: jax.Array | None = None,
    prefill: str = "auto",
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
      model: a :class:`fluxmpi_tpu.models.TransformerLM` (the TRAINING
        configuration — the decode twin is derived internally).
      params: its variables (``{"params": ...}``).
      prompt: int32 ``[batch, prompt_len]`` (``prompt_len >= 1``).
      max_new_tokens: continuation length; ``prompt_len + max_new_tokens``
        must fit ``model.max_len``.
      temperature: 0 = greedy argmax; > 0 = softmax sampling at that
        temperature (requires ``rng``).
      top_k: with sampling, restrict to the k highest-probability tokens
        before drawing.
      top_p: with sampling, nucleus filtering — keep the smallest set of
        highest-probability tokens whose cumulative probability reaches
        ``top_p`` (the most-probable token always survives). Composes
        with ``top_k`` (k-filter first, then the nucleus).
      eos_token: once a row emits this token, every later position in
        that row is forced to it (shapes stay static; the scan still
        runs ``max_new_tokens`` ticks).
      prefill: ``"batched"`` warms the KV cache with ONE causal forward
        over the prompt (:func:`prefill_cache`) and scans only the
        ``max_new_tokens`` decode ticks; ``"scan"`` teacher-forces the
        prompt through the original one-token-per-tick scan
        (O(prompt_len) sequential steps, but the whole loop is a single
        compiled program). For models whose batched forward is
        token-exact with single-position decoding (plain dense
        :class:`TransformerLM`) the two paths are bit-identical — the
        rng stream advances once per tick either way, so sampled
        continuations match too. ``"auto"`` (default) picks batched
        exactly for those models (``model.batched_prefill_safe``) and
        keeps the scan for the rest — MoE capacity routing can drop
        over-capacity prompt tokens in a batched forward that the
        one-token ticks never drop, so a silent switch would change
        MoE outputs.

    Returns:
      int32 ``[batch, prompt_len + max_new_tokens]`` — the prompt
      followed by the generated continuation.
    """
    b, plen = prompt.shape
    total = _validate_lengths(model, plen, max_new_tokens)
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and rng is None:
        raise ValueError("temperature > 0 requires an rng key")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    _validate_eos(model, eos_token)
    if prefill not in ("auto", "batched", "scan"):
        raise ValueError(
            f"prefill must be 'auto', 'batched', or 'scan', got {prefill!r}"
        )
    if prefill == "auto":
        prefill = (
            "batched"
            if getattr(model, "batched_prefill_safe", False)
            else "scan"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)

    twin = _decode_twin(model)
    prompt = prompt.astype(jnp.int32)

    def body(carry, _):
        cache, tok, pos, rng, done = carry
        logits, mutated = twin.apply(
            {"params": params["params"], "cache": cache},
            tok, train=False, pos_offset=pos, mutable=["cache"],
        )
        logits = logits[:, -1]  # [b, vocab]
        rng, sub = jax.random.split(rng)
        if temperature > 0:
            if top_k is not None and top_k < logits.shape[-1]:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            scaled = logits / temperature
            if top_p is not None and top_p < 1.0:
                # Nucleus: the kept set is a prefix of the descending
                # sort whose EXCLUSIVE cumulative probability is < p (so
                # the argmax token always survives); everything below
                # the prefix's smallest logit is masked.
                srt = jnp.sort(scaled, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(srt, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = (cum - probs) < top_p
                thresh = jnp.min(
                    jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True
                )
                scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        # Prefill: while the NEXT position is still inside the prompt,
        # teacher-force it (the cache warms up on prompt tokens).
        in_prompt = pos + 1 < plen
        forced = jax.lax.dynamic_slice_in_dim(
            prompt, jnp.minimum(pos + 1, plen - 1), 1, axis=1
        )[:, 0]
        nxt = jnp.where(in_prompt, forced, nxt).astype(jnp.int32)
        if eos_token is not None:
            nxt = jnp.where(done, jnp.int32(eos_token), nxt)
            done = done | ((nxt == eos_token) & jnp.logical_not(in_prompt))
        return (mutated["cache"], nxt[:, None], pos + 1, rng, done), nxt

    if prefill == "batched" and plen > 1:
        # Positions 0..plen-2 land in the cache in one forward; the scan
        # starts at the LAST prompt token (the first tick whose output
        # is a real continuation — identical to where the scan path's
        # teacher forcing ends). The scan path burns one rng split per
        # prompt tick; replay those splits so the decode-tick stream —
        # and therefore every sampled continuation — is bit-identical.
        cache, _ = prefill_cache(model, params, prompt[:, : plen - 1], total)
        for _ in range(plen - 1):
            rng, _ = jax.random.split(rng)
        init = (cache, prompt[:, plen - 1:], jnp.asarray(plen - 1), rng,
                jnp.zeros((b,), bool))
        _, toks = jax.lax.scan(body, init, None, length=max_new_tokens)
        # toks: [max_new_tokens, b] — tokens for positions plen..total-1.
        return jnp.concatenate([prompt, toks.T], axis=1)

    cache = _sized_cache(twin, b, total)
    init = (cache, prompt[:, :1], jnp.asarray(0), rng,
            jnp.zeros((b,), bool))
    _, toks = jax.lax.scan(body, init, None, length=total - 1)
    # toks: [total-1, b] — tokens for positions 1..total-1.
    return jnp.concatenate([prompt[:, :1], toks.T], axis=1)


def beam_search(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    beam_size: int,
    length_penalty: float = 0.0,
    eos_token: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding: the highest-scoring continuation under the
    model's log-likelihood, explored ``beam_size`` hypotheses at a time.

    Completes the inference surface next to :func:`generate`'s sampling
    modes (the reference is training-only — its models are user-land
    Flux code — so this, like ``generate``, is "complete framework"
    surface beyond parity). Built the TPU way:

    - The prompt prefills the KV cache on ``batch`` rows (one
      teacher-forced scan), and only then does the cache repeat into
      ``rows = batch * beam_size`` — the beam loop never re-runs prompt
      work ``beam_size`` times over.
    - Beams fold into the batch dimension, so every decode tick is ONE
      batched forward on the KV cache — no per-beam loops.
    - Beam reordering is a static-shape gather: the token matrix, the
      cumulative scores, and every cache array with a leading ``rows``
      dim are re-indexed by the selected parents each tick (flax's
      scalar ``cache_index`` passes through untouched).
    - The search is two ``lax.scan`` s (prefill + beam loop) — static
      shapes, single compiled program, no host round trips.

    Finished hypotheses are absorbed rather than swapped out: once a
    beam emits ``eos_token`` its only legal continuation is ``eos`` at
    zero added log-probability, so its score freezes while shapes stay
    static. Candidates are RANKED by the GNMT-penalized score
    ``cum_logp / ((5 + L) / 6) ** length_penalty`` both during pruning
    (``L`` = frozen finish length for finished beams, tokens-so-far for
    live ones — all live candidates at a tick share the same ``L``, so
    within-live order matches raw log-probability) and at final
    selection; the returned score uses the same formula.
    ``length_penalty=0`` reduces everything to raw summed
    log-probability.

    Args:
      model: a :class:`fluxmpi_tpu.models.TransformerLM` (training
        configuration — the decode twin is derived internally).
      params: its variables (``{"params": ...}``).
      prompt: int32 ``[batch, prompt_len]`` (``prompt_len >= 1``).
      max_new_tokens: continuation length; ``prompt_len +
        max_new_tokens`` must fit ``model.max_len``.
      beam_size: hypotheses kept per batch row (>= 1; ``beam_size=1``
        reduces to greedy :func:`generate`).
      length_penalty: GNMT alpha; > 0 favors longer finished hypotheses.
      eos_token: absorbing end-of-sequence token (see above). Without
        it every hypothesis runs the full ``max_new_tokens``.

    Returns:
      ``(tokens, scores)`` — int32 ``[batch, prompt_len +
      max_new_tokens]`` best sequence per batch row (positions after a
      hypothesis' ``eos`` are ``eos``), and float32 ``[batch]`` its
      length-penalized log-probability score.
    """
    b, plen = prompt.shape
    total = _validate_lengths(model, plen, max_new_tokens)
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    _validate_eos(model, eos_token)

    beam = int(beam_size)
    rows = b * beam
    vocab = model.vocab_size
    alpha = float(length_penalty)
    twin = _decode_twin(model)
    prompt = prompt.astype(jnp.int32)

    def _lp(length):
        return ((5.0 + length.astype(jnp.float32)) / 6.0) ** alpha

    # --- Prefill: teacher-force the prompt on b rows, then repeat the
    # warmed cache into b*beam rows (beam-contiguous per batch row, to
    # match the flat index used by the reorder gather below). ----------
    cache = _sized_cache(twin, b, total)

    def pf_body(carry, tok):
        cache, pos = carry
        _, mutated = twin.apply(
            {"params": params["params"], "cache": cache},
            tok[:, None], train=False, pos_offset=pos, mutable=["cache"],
        )
        return (mutated["cache"], pos + 1), None

    (cache, _), _ = jax.lax.scan(
        pf_body, (cache, jnp.asarray(0)), prompt[:, : plen - 1].T
    )
    cache = jax.tree_util.tree_map(
        lambda x: jnp.repeat(x, beam, axis=0)
        if x.ndim >= 1 and x.shape[0] == b else x,
        cache,
    )

    toks0 = jnp.zeros((b, beam, total), jnp.int32)
    toks0 = toks0.at[:, :, :plen].set(prompt[:, None, :])
    # Only beam 0 is live at the start — identical hypotheses must not
    # fill the whole beam with duplicates on the first expansion.
    cum0 = jnp.full((b, beam), -jnp.inf, jnp.float32).at[:, 0].set(0.0)
    done0 = jnp.zeros((b, beam), bool)
    flen0 = jnp.full((b, beam), max_new_tokens, jnp.int32)

    def _reorder_cache(cache, parent):
        flat = (parent + jnp.arange(b)[:, None] * beam).reshape(rows)
        return jax.tree_util.tree_map(
            lambda x: x[flat] if x.ndim >= 1 and x.shape[0] == rows else x,
            cache,
        )

    def body(carry, _):
        cache, toks, cum, done, flen, pos = carry
        tok = jax.lax.dynamic_slice_in_dim(
            toks.reshape(rows, total), pos, 1, axis=1
        )
        logits, mutated = twin.apply(
            {"params": params["params"], "cache": cache},
            tok, train=False, pos_offset=pos, mutable=["cache"],
        )
        cache = mutated["cache"]
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1
        ).reshape(b, beam, vocab)
        if eos_token is not None:
            # Absorbing state: a finished beam continues only as eos, at
            # zero added log-probability (its score freezes).
            eos_row = jnp.full((vocab,), -jnp.inf, jnp.float32)
            eos_row = eos_row.at[int(eos_token)].set(0.0)
            logp = jnp.where(done[:, :, None], eos_row[None, None], logp)
        raw = (cum[:, :, None] + logp).reshape(b, beam * vocab)
        gen_count = pos + 2 - plen  # generated tokens incl. this tick's
        if alpha != 0.0:
            # Prune on the penalized score the function optimizes:
            # finished parents keep their frozen length, live candidates
            # use tokens-so-far (identical across vocab, so the penalty
            # is per-beam).
            pen = _lp(jnp.where(done, flen, gen_count))  # [b, beam]
            rank = (
                raw.reshape(b, beam, vocab) / pen[:, :, None]
            ).reshape(b, beam * vocab)
        else:
            rank = raw
        _, top_idx = jax.lax.top_k(rank, beam)
        cum = jnp.take_along_axis(raw, top_idx, axis=1)
        parent = top_idx // vocab
        token = (top_idx % vocab).astype(jnp.int32)

        toks = jnp.take_along_axis(toks, parent[:, :, None], axis=1)
        toks = jax.lax.dynamic_update_slice_in_dim(
            toks, token[:, :, None], pos + 1, axis=2
        )
        done = jnp.take_along_axis(done, parent, axis=1)
        flen = jnp.take_along_axis(flen, parent, axis=1)
        if eos_token is not None:
            ends_now = (token == eos_token) & jnp.logical_not(done)
            flen = jnp.where(ends_now, gen_count, flen)
            done = done | (token == eos_token)
        cache = _reorder_cache(cache, parent)
        return (cache, toks, cum, done, flen, pos + 1), None

    init = (cache, toks0, cum0, done0, flen0, jnp.asarray(plen - 1))
    (_, toks, cum, _, flen, _), _ = jax.lax.scan(
        body, init, None, length=max_new_tokens
    )
    scored = cum / _lp(flen)
    best = jnp.argmax(scored, axis=1)
    out = jnp.take_along_axis(toks, best[:, None, None], axis=1)[:, 0]
    return out, jnp.take_along_axis(scored, best[:, None], axis=1)[:, 0]
