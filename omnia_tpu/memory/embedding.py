"""Embedding providers + async re-embed backfill.

The reference resolves an embedding Provider CRD and calls a remote API
(reference internal/memory/embedding.go, reembed_worker.go). Here the
embedding role runs on-device: TpuEmbedder jits the model's masked
mean-pool forward (models/llama.py forward_embed) over bucketed batch
shapes, so memory writes never trigger a compile. HashingEmbedder is the
deterministic no-model stand-in (the mock-provider analog) used by tests
and clusterless dev.

ReembedWorker mirrors the reference's async backfill: writes land with
embedding=NULL and a background worker embeds them in batches, so the
write path never blocks on the accelerator.
"""

from __future__ import annotations

import hashlib
import logging
import threading
from typing import Optional, Sequence

import numpy as np

from omnia_tpu.memory.store import MemoryStore, tokenize

logger = logging.getLogger(__name__)


class Embedder:
    dim: int

    def embed(self, texts: Sequence[str]) -> np.ndarray:  # [N, dim] unit rows
        raise NotImplementedError


class HashingEmbedder(Embedder):
    """Deterministic feature-hashing embedder: words + char trigrams hashed
    into `dim` buckets, tf-weighted, L2-normalized. No model, no RNG —
    stable across processes, good lexical-overlap semantics for tests."""

    def __init__(self, dim: int = 256):
        self.dim = dim

    def _features(self, text: str) -> list[str]:
        words = tokenize(text)
        feats = list(words)
        for w in words:
            padded = f"^{w}$"
            feats.extend(padded[i : i + 3] for i in range(len(padded) - 2))
        return feats

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), dtype=np.float32)
        for i, text in enumerate(texts):
            for feat in self._features(text):
                h = int.from_bytes(hashlib.blake2b(feat.encode(), digest_size=8).digest(), "little")
                sign = 1.0 if (h >> 63) & 1 else -1.0
                out[i, h % self.dim] += sign
            n = float(np.linalg.norm(out[i]))
            if n > 0:
                out[i] /= n
        return out


class TpuEmbedder(Embedder):
    """On-device embedder: tokenizer + jitted forward_embed, batch/length
    bucketed so every call hits a warm compile-cache entry."""

    LEN_BUCKETS = (32, 128, 512)
    BATCH_BUCKETS = (1, 8, 32)

    def __init__(self, params, cfg, tokenizer, mesh=None):
        import jax

        from omnia_tpu.models import llama

        self._tokenizer = tokenizer
        self._params = params
        self._cfg = cfg
        self.dim = cfg.hidden_size
        self._fn = jax.jit(lambda tok, mask: llama.forward_embed(params, cfg, tok, mask, mesh))

    def _bucket(self, n: int, buckets) -> int:
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        import numpy as np

        max_b = self.BATCH_BUCKETS[-1]
        out = []
        for start in range(0, len(texts), max_b):
            out.append(self._embed_batch(texts[start : start + max_b]))
        return np.concatenate(out) if out else np.zeros((0, self.dim), dtype=np.float32)

    def _embed_batch(self, texts: Sequence[str]) -> np.ndarray:
        import numpy as np

        ids = [self._tokenizer.encode(t)[: self.LEN_BUCKETS[-1]] for t in texts]
        T = self._bucket(max((len(x) for x in ids), default=1), self.LEN_BUCKETS)
        B = self._bucket(len(ids), self.BATCH_BUCKETS)
        tok = np.zeros((B, T), dtype=np.int32)
        mask = np.zeros((B, T), dtype=np.int32)
        for i, row in enumerate(ids):
            tok[i, : len(row)] = row
            mask[i, : len(row)] = 1
        vecs = np.asarray(self._fn(tok, mask))
        return vecs[: len(texts)]


class ReembedWorker:
    """Background embedding backfill: drains store.pending_embeddings in
    batches until none remain (reference reembed_worker.go)."""

    def __init__(self, store: MemoryStore, embedder: Embedder, batch: int = 16, interval_s: float = 0.5):
        self.store = store
        self.embedder = embedder
        self.batch = batch
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.embedded_total = 0

    def run_once(self) -> int:
        pending = self.store.pending_embeddings(self.batch)
        if not pending:
            return 0
        texts = [
            " ".join([e.content] + [o.content for o in e.observations])
            for e in pending
        ]
        try:
            vecs = self.embedder.embed(texts)
        except Exception:  # noqa: BLE001 — backfill must never kill the service
            logger.exception("embed batch failed; will retry")
            return 0
        for e, v in zip(pending, vecs):
            self.store.set_embedding(e.id, v)
        self.embedded_total += len(pending)
        return len(pending)

    def drain(self, max_batches: int = 1000) -> int:
        total = 0
        for _ in range(max_batches):
            n = self.run_once()
            total += n
            if n == 0:
                break
        return total

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                if self.run_once() == 0:
                    self._stop.wait(self.interval_s)

        self._thread = threading.Thread(target=loop, name="reembed-worker", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
