"""Batched serving runtime with PERKS persistent decode.

Requests accumulate into a batch; the engine prefills them together and
generates through the PERKS executor: it wraps the batch as a
:class:`repro.exec.DecodeAttentionProblem`, asks ``plan()`` for the tier
(plans are cached per ``batch_key``, so steady-state serving re-plans
only when shapes change), and runs ``execute()`` — the resident tier is
``Model.decode_loop``, N tokens per dispatch with a donated cache (the
paper's persistent-kernel execution applied to serving). The baseline
mode dispatches ``decode_step`` per token for the benchmark comparison
(benchmarks/decode_bench.py).

:func:`start_metrics_server` exposes any :class:`repro.obs.MetricsRegistry`
(the ambient one by default) over HTTP in the Prometheus text exposition
format — point a scraper at ``GET /metrics`` (DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
import http.server
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.lm import Model


class MetricsServer:
    """A daemon-threaded HTTP server serving one registry at /metrics."""

    def __init__(self, registry: obs.MetricsRegistry, host: str, port: int):
        self.registry = registry

        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path.rstrip("/") != "/metrics":
                    self.send_error(404, "only /metrics is served here")
                    return
                body = server.registry.prometheus_text().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):     # scrapes are not stdout events
                pass

        self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def url(self) -> str:
        return f"http://{self.host}:{self.port}/metrics"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def start_metrics_server(registry: Optional[obs.MetricsRegistry] = None, *,
                         host: str = "127.0.0.1",
                         port: int = 0) -> MetricsServer:
    """Serve ``registry`` (default: the ambient metrics registry) at
    ``GET /metrics`` in Prometheus text format. ``port=0`` picks a free
    port (read it back from ``.port``). The server runs on a daemon
    thread; call ``.close()`` (or use as a context manager) to stop."""
    if registry is None:
        registry = obs.get_metrics()
    return MetricsServer(registry, host, port)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (prompt_len,) int32
    max_new_tokens: int = 32


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    persistent: bool = True      # PERKS decode_loop vs per-token host loop
    tokens_per_dispatch: int = 32


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig = ServeConfig(),
                 *, metrics: Optional[obs.MetricsRegistry] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.metrics = metrics if metrics is not None else obs.MetricsRegistry()
        self._queue: list[Request] = []
        self._prefill = jax.jit(
            lambda p, b, n: model.prefill(p, b, cache_seq=n),
            static_argnums=(2,))
        self._decode_step = jax.jit(model.decode_step, donate_argnums=(1,))
        # plan cache: batch_key -> Plan. Serving the same shapes again
        # reuses the planner's decision instead of re-ranking candidates.
        self._plans: dict = {}

    def submit(self, req: Request):
        self._queue.append(req)

    def run_batch(self) -> tuple[np.ndarray, dict]:
        """Serve up to max_batch queued requests (padded to equal prompt
        length). Returns (generated tokens (B, max_new), stats)."""
        batch = self._queue[:self.cfg.max_batch]
        self._queue = self._queue[self.cfg.max_batch:]
        assert batch, "no queued requests"
        plen = max(len(r.prompt) for r in batch)
        new = max(r.max_new_tokens for r in batch)
        prompts = np.stack([
            np.pad(r.prompt, (plen - len(r.prompt), 0)) for r in batch
        ]).astype(np.int32)

        t0 = time.time()
        total = plen + new
        logits, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(prompts)}, total)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        first.block_until_ready()
        t_prefill = time.time() - t0

        t0 = time.time()
        tier = None
        if self.cfg.persistent:
            # the executor path: wrap the batch as a Problem, let the
            # planner pick the tier (resident = decode_loop; a VMEM-
            # overflowing batch demotes to device_loop, still one fused
            # program), execute. Token-identical to the legacy loop on
            # every tier (tests/test_ml_problems.py).
            from repro.exec import DecodeAttentionProblem, execute, plan
            prob = DecodeAttentionProblem(
                model=self.model, params=self.params, cache=cache,
                first_tokens=first, n_steps=new - 1)
            key = prob.batch_key()
            eplan = self._plans.get(key)
            if eplan is None:
                eplan = plan(prob)
                self._plans[key] = eplan
            tier = eplan.tier
            toks, cache = execute(prob, eplan)
            out = np.concatenate([np.asarray(first)[:, None],
                                  np.asarray(toks)], axis=1)
        else:
            out_list = [np.asarray(first)]
            tok = first
            for _ in range(new - 1):
                logits, cache = self._decode_step(self.params, cache, tok)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out_list.append(np.asarray(tok))
            out = np.stack(out_list, axis=1)
        t_decode = time.time() - t0
        mode = "persistent" if self.cfg.persistent else "host_loop"
        mx = self.metrics
        mx.counter("server_batches_total", mode=mode).inc()
        mx.counter("server_tokens_total", mode=mode).inc(len(batch) * new)
        mx.counter("server_prefill_s_total").inc(t_prefill)
        mx.counter("server_decode_s_total", mode=mode).inc(t_decode)
        stats = {
            "batch": len(batch),
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tok_per_s": len(batch) * new / max(t_decode, 1e-9),
            "mode": mode,
            "tier": tier,
        }
        return out, stats
