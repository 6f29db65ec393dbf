"""Disaggregated prefill/decode: KV handoff between specialist replicas.

Prefill and decode want different machines: prefill is compute-bound
(one big attention pass over the prompt), decode is cache-bound (stream
weights + KV per token). The fleet's disaggregated roles split them —
a PREFILL replica runs the prompt once and fills content-hashed pages;
a DECODE replica adopts those pages into its own ``PagePool`` and
serves the tokens without ever touching the prompt's prefill.

The transport rides two existing invariants instead of inventing new
machinery:

- **Pages already have identity.** Prefix sharing keys a page by the
  byte-hash of the prompt head it covers (``PagePool._key``); a page is
  shareable iff it ends strictly before the first decode write, so its
  contents are a pure function of the token prefix. Shipping a page is
  therefore just shipping (tokens-it-covers, K/V tensors) — the decode
  side re-registers it under the SAME content hash and ``match_prefix``
  finds it exactly as if a local tenant had prefilled it.
- **The checkpoint store already does integrity.** The handoff file is
  a checkpoint (``tpudml.checkpoint.store``, format 2): per-leaf
  CRC-32, atomic tmp+rename, and a loud ``CheckpointCorruptError`` on
  truncation/bitflip — so a vandalized handoff is REJECTED at adopt and
  the request transparently falls back to local prefill (no prefix hit,
  same tokens, just slower). ``faults.vandalize`` works on handoff
  directories unmodified, which is exactly how the rollback test
  injects the truncation.

Greedy parity is byte-exact by construction: adopted pages hold
bitwise-identical K/V to what local prefill would have written (same
params, same compiled prefill programs, same positions), so the decode
replica's token stream equals the single-engine stream token-for-token
— pinned in tests/test_fleet_disagg.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpudml.checkpoint.store import (
    CheckpointCorruptError,
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
    _read_manifest,
)
from tpudml.capabilities import reject
from tpudml.serve.engine import RequestStats, ServeConfig, ServingEngine
from tpudml.serve.load import Request
from tpudml.serve.paged import PagedKVCache

HANDOFF_VERSION = 1


def _require_paged_sharing(cfg: ServeConfig, who: str):
    if cfg.cache_layout != "paged" or not cfg.prefix_sharing:
        raise ValueError(
            f"{who} requires cache_layout='paged' with prefix_sharing=True "
            f"(content-hashed pages are the handoff unit)"
        )


def write_handoff(model, params, cfg: ServeConfig, prompt,
                  directory) -> dict:
    """PREFILL role: run ``prompt``'s prefill on a 1-slot paged engine
    and serialize its shareable pages (the whole-page prompt prefix)
    through the CRC-verified checkpoint format under ``directory``.

    Returns ``{"n_pages", "covered_tokens", "path"}`` — ``n_pages`` may
    be 0 for a sub-page prompt (nothing shareable; adopt is a no-op and
    decode falls back to local prefill)."""
    if getattr(model, "stateful", False):
        reject("serve_pattern_handoff")
    _require_paged_sharing(cfg, "write_handoff")
    prompt = np.asarray(prompt, np.int32)
    if prompt.ndim != 1 or prompt.size < 1:
        raise ValueError("prompt must be [L>=1]")
    ecfg = ServeConfig(
        slots=1,
        max_len=cfg.max_len,
        prefill_chunk=cfg.prefill_chunk,
        cache_kind=cfg.cache_kind,
        cache_layout="paged",
        page_size=cfg.page_size,
        prefix_sharing=True,
        step_time_s=cfg.step_time_s,
        weight_quant=cfg.weight_quant,
    )
    if prompt.size + 1 > ecfg.max_len:
        raise ValueError(
            f"prompt {prompt.size} + 1 exceeds max_len {ecfg.max_len}"
        )
    eng = ServingEngine(model, params, ecfg)
    st = RequestStats(
        rid=0, prompt_len=prompt.size, max_new_tokens=1, arrival=0.0
    )
    admitted = eng._admit_paged(
        0, Request(rid=0, prompt=prompt, max_new_tokens=1), st
    )
    assert admitted is not None  # a fresh pool cannot be starved
    p = prompt.size - 1  # first decode write position
    pages = eng._slot_pages[0]
    n = sum(1 for j in range(len(pages))
            if (j + 1) * ecfg.page_size <= p)
    pids = np.asarray(pages[:n], np.int32)
    kind = ecfg.cache_kind
    has_scales = kind == "int8"

    def gather(field_name):
        return np.stack([
            np.asarray(jax.device_get(getattr(c, field_name)[pids]))
            for c in eng.caches
        ]) if n else np.zeros((0,), np.float32)

    payload = {
        "prompt_head": prompt[: n * ecfg.page_size],
        "k": gather("k"),
        "v": gather("v"),
        "k_scale": gather("k_scale") if has_scales else np.zeros((0,), np.float32),
        "v_scale": gather("v_scale") if has_scales else np.zeros((0,), np.float32),
    }
    meta = {
        "fleet_handoff": HANDOFF_VERSION,
        "page_size": ecfg.page_size,
        "cache_kind": kind,
        "n_pages": int(n),
        "num_layers": len(eng.caches),
        "covered_tokens": int(n * ecfg.page_size),
    }
    path = save_checkpoint(directory, payload, 0, metadata=meta)
    return {"n_pages": int(n), "covered_tokens": meta["covered_tokens"],
            "path": path}


def adopt_handoff(engine: ServingEngine, directory, *,
                  strict: bool = False) -> int:
    """DECODE role: verify + load a handoff directory and graft its
    pages into ``engine``'s pool under their content hashes; returns
    the number of pages adopted.

    0 means "serve without the handoff": missing/empty handoff, a
    CRC-failed (vandalized) file, or a pool too full to take the pages
    — in every case the next matching request simply finds no prefix
    hit and prefills locally (correctness never depends on adoption;
    only prefill work does). ``strict=True`` re-raises the corruption
    instead, for callers that want the loud version. Config mismatches
    (page size / cache kind / layer count) always raise — that is a
    wiring bug, not a fault."""
    _require_paged_sharing(engine.cfg, "adopt_handoff")
    path = latest_checkpoint(directory)
    if path is None:
        if strict:
            raise CheckpointCorruptError(f"{directory}: no handoff found")
        return 0
    try:
        meta = _read_manifest(path).get("metadata", {})
    except CheckpointCorruptError:
        if strict:
            raise
        return 0
    if meta.get("fleet_handoff") != HANDOFF_VERSION:
        raise ValueError(
            f"handoff version {meta.get('fleet_handoff')!r} != "
            f"{HANDOFF_VERSION}"
        )
    cfg = engine.cfg
    if (meta.get("page_size") != cfg.page_size
            or meta.get("cache_kind") != cfg.cache_kind
            or meta.get("num_layers") != len(engine.caches)):
        raise ValueError(
            f"handoff/engine mismatch: handoff (page_size="
            f"{meta.get('page_size')}, kind={meta.get('cache_kind')}, "
            f"layers={meta.get('num_layers')}) vs engine (page_size="
            f"{cfg.page_size}, kind={cfg.cache_kind}, "
            f"layers={len(engine.caches)})"
        )
    n = int(meta.get("n_pages", 0))
    if n == 0:
        return 0
    layers = len(engine.caches)
    c0 = engine.caches[0]
    _, psz, hkv, dh = c0.k.shape
    has_scales = cfg.cache_kind == "int8"
    target = {
        "prompt_head": np.zeros(n * cfg.page_size, np.int32),
        "k": np.zeros((layers, n, psz, hkv, dh), c0.k.dtype),
        "v": np.zeros((layers, n, psz, hkv, dh), c0.v.dtype),
        "k_scale": (np.zeros((layers, n, psz, hkv), np.float32)
                    if has_scales else np.zeros((0,), np.float32)),
        "v_scale": (np.zeros((layers, n, psz, hkv), np.float32)
                    if has_scales else np.zeros((0,), np.float32)),
    }
    try:
        payload = restore_checkpoint(path, target, verify=True)
    except CheckpointCorruptError:
        if strict:
            raise
        return 0
    pool = engine._pool
    pids = pool.alloc_n(n)
    if pids is None:
        return 0  # pool under pressure; local prefill still works
    idx = jnp.asarray(np.asarray(pids, np.int32))
    caches = []
    for l, c in enumerate(engine.caches):
        k = c.k.at[idx].set(jnp.asarray(payload["k"][l]))
        v = c.v.at[idx].set(jnp.asarray(payload["v"][l]))
        k_sc, v_sc = c.k_scale, c.v_scale
        if has_scales:
            k_sc = k_sc.at[idx].set(jnp.asarray(payload["k_scale"][l]))
            v_sc = v_sc.at[idx].set(jnp.asarray(payload["v_scale"][l]))
        caches.append(
            PagedKVCache(k=k, v=v, k_scale=k_sc, v_scale=v_sc, kind=c.kind)
        )
    engine.caches = caches
    prompt_head = np.asarray(payload["prompt_head"], np.int32)
    for j, pid in enumerate(pids):
        # Publish under the content hash, then release: a keyed page at
        # refcount 0 parks in the retained-LRU — exactly the state a
        # local tenant's shareable pages reach after eviction, so
        # ``match_prefix`` serves it to the next matching prompt.
        pool.register(pid, prompt_head, j)
        pool.release(pid)
    return n
