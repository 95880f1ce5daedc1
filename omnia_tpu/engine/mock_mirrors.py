"""Mock-engine host-side subsystem mirrors (int8-KV, spec, paged-KV).

Mixin methods of :class:`~omnia_tpu.engine.mock.MockEngine` (split out
on the file-length discipline; one lock group with mock.py). Each
mirror drives a real subsystem's ledger host-side — the SAME rowwise
quantize/dequant numerics, the SAME bounded n-gram index/depth
policy/gate, the SAME page allocator the engine books with — so
hermetic tests exercise identical metrics with no device. Scripted
token output is EXACTLY unchanged by every mirror. All of it is
jax-free: the CI analysis job runs the mirror batteries under a
poisoned jax stub.
"""

from __future__ import annotations

from typing import Optional


class _MockMirrorsMixin:
    def _kv_roundtrip(self, token_ids: list[int]) -> None:
        """Quantize→dequantize a deterministic pseudo-KV block derived
        from the token stream (one row per token, 4 heads × 16 dims) and
        record the drift — the host-side mirror of what every KV write
        in the compiled programs does to real rows."""
        if not self.kv_quant or not token_ids:
            return
        import numpy as np

        from omnia_tpu.models.kv_quant import (
            dequantize_rows_np,
            quantize_rows_np,
        )

        ids = np.asarray(token_ids, np.float32)
        rows = np.sin(
            ids[:, None, None] * 0.1
            + np.arange(4, dtype=np.float32)[None, :, None] * 0.7
            + np.arange(16, dtype=np.float32)[None, None, :] * 0.31
        ).astype(np.float32)
        back = dequantize_rows_np(quantize_rows_np(rows))
        rel = float(
            np.max(np.abs(back - rows)) / max(float(np.max(np.abs(rows))), 1e-9)
        )
        with self._lock:
            self.metrics["kv_quant_rows_written"] += len(token_ids)
            self.metrics["kv_quant_roundtrip_rel_err"] = max(
                self.metrics["kv_quant_roundtrip_rel_err"], rel
            )

    def _spec_mirror(self, prompt_tokens, reply_ids, params) -> None:
        """Walk a greedy playback's reply in verify-window strides
        through the real prompt-lookup machinery: propose from the
        bounded n-gram index over prompt+emitted, accept the prefix
        matching the scripted reply (the mock's stand-in for the
        model's greedy choices), update the real per-slot depth policy,
        and tick the real gate — so the spec ledger and controllers are
        exercisable hermetically. Playback output is untouched."""
        if self.spec_decode <= 0 or params.temperature != 0.0:
            return
        import time as _time

        from omnia_tpu.engine.spec_decode import (
            _EMA_ALPHA,
            _ENTRY_BYTES,
            _NgramIndex,
            spec_depth_update,
        )

        idx = _NgramIndex()
        kmax = self.spec_decode_max
        k = min(self.spec_decode, kmax) if kmax else self.spec_decode
        ema = (k / kmax) if kmax else 1.0
        ctx = list(prompt_tokens)
        pos, steps, proposed, accepted = 0, 0, 0, 0
        while pos < len(reply_ids):
            if self._spec_gate is not None:
                # The gate is shared across concurrent playbacks —
                # tick under the lock (the engine's gate is engine-
                # thread-only and needs none), against the cumulative
                # walked-token counter, never this playback's position.
                with self._lock:
                    allowed = self._spec_gate.tick(
                        _time.monotonic(), self._spec_walked
                    )
                if not allowed:
                    ctx.append(reply_ids[pos])
                    pos += 1
                    with self._lock:
                        self._spec_walked += 1
                    continue
            prop, real = idx.propose(ctx, max(k, 1))
            acc = 0
            while (acc < real and pos + acc < len(reply_ids)
                   and prop[acc] == reply_ids[pos + acc]):
                acc += 1
            emit = min(acc + 1, len(reply_ids) - pos)  # accepted + bonus
            ctx.extend(reply_ids[pos:pos + emit])
            pos += emit
            if self._spec_gate is not None:
                with self._lock:
                    self._spec_walked += emit
            if real > 0:
                steps += 1
                proposed += real
                accepted += acc
                ema, new_k = spec_depth_update(ema, real, acc, kmax)
                if kmax:
                    k = max(new_k, 1)  # mirror skips the re-probe wait
        with self._lock:
            self.metrics["spec_steps"] += steps
            self.metrics["spec_proposed"] += proposed
            self.metrics["spec_accepted"] += accepted
            if proposed:
                self._spec_ema += _EMA_ALPHA * (
                    accepted / proposed - self._spec_ema
                )
                self.metrics["spec_accept_ema"] = round(self._spec_ema, 4)
            self.metrics["spec_index_bytes"] = _ENTRY_BYTES * idx.entries()
            if self._spec_gate is not None:
                self.metrics["spec_gate_state"] = self._spec_gate.state_code()

    def _page_mirror_begin(self, n_prompt: int) -> Optional[int]:
        """Reserve pages for a live playback's prompt rows (paged-KV
        parity). None when the mirror is off or saturated — playback
        proceeds either way; the mirror only drives the gauges."""
        if self._page_alloc is None:
            return None
        with self._lock:
            if not self._page_slots:
                return None
            a = self._page_alloc
            slot = self._page_slots.pop()
            rows = min(n_prompt, a.page_tokens * a.total)
            if a.writes_needed(slot, 0, rows) <= a.free_count:
                a.prepare_write(slot, 0, rows)
            self.metrics["kv_pages_free"] = a.free_count
            self.metrics["kv_page_fragmentation"] = a.fragmentation()
            self.metrics["kv_page_cow_copies"] = a.cow_copies
            return slot

    def _page_mirror_end(self, slot: Optional[int]) -> None:
        if slot is None:
            return
        with self._lock:
            a = self._page_alloc
            a.release_from(slot, 0)
            self._page_slots.append(slot)
            self.metrics["kv_pages_free"] = a.free_count
            self.metrics["kv_page_fragmentation"] = a.fragmentation()
            self.metrics["kv_page_cow_copies"] = a.cow_copies
