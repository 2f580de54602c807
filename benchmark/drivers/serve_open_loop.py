"""Driver ``serve_open_loop``: an LM endpoint under open-loop HTTP load.

Set-up exports the configuration's weights (made on the device from the
seed, in the served type) through ``registry.save_flax``, defines the
endpoint with ``serving.create_or_update(model_server="LM")`` and hosts
it in this process with ``serving.start`` (a child would take the chip
and could not be traced). Load comes from ``harness/traffic_gen.py``
through ``harness/pacer.py``: every request is one HTTP POST, timed at
the client from its due instant. The endpoint does not stream, so the
client sees one latency per request.
"""

from __future__ import annotations

import gc
import http.client
import json
import shutil
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import pacer, stats, traffic_gen

NAME = "bench_lm"


class Endpoint:
    """The served model: set up once, offered load any number of times
    (the knee sweep reuses one set-up), stopped once."""

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg, self.traffic, self.adapter = ctx.config, ctx.traffic, ctx.adapter
        self.vocab = int(self.cfg["module"]["vocab_size"])
        self.port: int | None = None
        self._local = threading.local()
        self.reduced_trace: dict[str, Any] | None = None

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from hops_tpu.modelrepo import registry, serving
        from hops_tpu.runtime import config as rt_config

        ctx, cfg = self.ctx, self.cfg
        ws = ctx.cache_dir / f"ws-{cfg['name']}-s{ctx.seed}"
        for stale in ctx.cache_dir.glob(f"ws-{cfg['name']}-s*"):
            if stale != ws:  # one bundle per configuration on disk: it is the size of the model
                shutil.rmtree(stale, ignore_errors=True)
        rt_config.configure(workspace=str(ws), project="bench")
        marker = ws / "bundle.ok"
        if not marker.exists():
            t0 = time.perf_counter()
            shutil.rmtree(ws, ignore_errors=True)
            ws.mkdir(parents=True)
            model = self.adapter.build_module(cfg)
            params = jax.device_get(self.adapter.init_served_params(cfg, model, ctx.seed))
            registry.save_flax(model, params, NAME)
            del params
            gc.collect()
            marker.write_text("ok")
            ctx.note(f"bundle written in {time.perf_counter() - t0:.1f}s under {ws}")
        else:
            ctx.note(f"bundle reused from {ws}")
        serving.create_or_update(NAME, model_name=NAME, model_version=1, model_server="LM",
                                 lm_config=dict(cfg["serving"]["lm_config"]))
        t0 = time.perf_counter()
        self.port = int(serving.start(NAME)["port"])
        ctx.note(f"serving.start {time.perf_counter() - t0:.1f}s, port {self.port}")
        rs = np.random.RandomState(ctx.seed + 104729)
        for w in self.traffic["warmup"]:
            prompt = rs.randint(0, self.vocab, int(w["prompt_len"])).tolist()
            row = self._post(prompt, int(w["max_new_tokens"]), None)
            if row.get("status") != 200:
                raise RuntimeError(f"warm-up request failed: {row}")

    def stop(self) -> None:
        from hops_tpu.modelrepo import serving

        serving.stop(NAME)

    def engine(self):
        from hops_tpu.modelrepo import serving

        return serving._servers[NAME].predictor._engine

    # -- one HTTP client per worker thread --------------------------------------

    def _conn(self) -> http.client.HTTPConnection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = self._local.conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=float(self.traffic["request_timeout_s"]))
        return conn

    def _request(self, method: str, path: str, body: bytes | None = None,
                 headers: dict[str, str] | None = None) -> tuple[int, bytes]:
        for attempt in (0, 1):
            conn = self._conn()
            try:
                conn.request(method, path, body=body, headers=headers or {})
                resp = conn.getresponse()
                return resp.status, resp.read()
            except (http.client.RemoteDisconnected, ConnectionResetError, BrokenPipeError):
                # a kept-alive connection the server has closed: reconnect once
                conn.close()
                self._local.conn = None
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def _post(self, prompt: list[int], max_new_tokens: int, trace_id: str | None) -> dict[str, Any]:
        headers = {"Content-Type": "application/json"}
        if trace_id:
            headers["traceparent"] = f"00-{trace_id}-{trace_id[:16]}-01"
        body = json.dumps({"instances": [{
            "prompt": prompt, "max_new_tokens": max_new_tokens, "eos_id": None}]}).encode()
        with jax.profiler.TraceAnnotation("bench:http_wait"):
            status, raw = self._request("POST", f"/v1/models/{NAME}:predict", body, headers)
        row: dict[str, Any] = {"status": status}
        if status == 200:
            row["tokens"] = json.loads(raw)["predictions"][0]
        else:
            row["error"] = raw[:200].decode(errors="replace")
        return row

    def _ttft_ms(self, trace_id: str) -> float | None:
        """Engine-side time to first token of one request, from the
        program's ``lm_engine.dispatch`` span (traced run only)."""
        status, raw = self._request("GET", f"/debug/traces/{trace_id}")
        if status != 200:
            return None
        for span in json.loads(raw).get("spans", []):
            if span["name"] == "lm_engine.dispatch" and "ttft_ms" in span["attrs"]:
                return float(span["attrs"]["ttft_ms"])
        return None

    def engine_stats(self) -> dict[str, Any]:
        status, raw = self._request("GET", f"/v1/models/{NAME}")
        if status != 200:
            raise RuntimeError(f"model status {status}")
        return json.loads(raw)["engine"]

    # -- load -------------------------------------------------------------------

    def offer(self, seconds: float, seed: int, *, trace: bool = False) -> dict[str, Any]:
        """Offer the mix for ``seconds`` and wait for the answers."""
        ctx, traffic = self.ctx, self.traffic
        requests = traffic_gen.make_requests(traffic, seconds, seed, self.vocab)

        def send(i: int) -> dict[str, Any]:
            req = requests[i]
            trace_id = f"{(seed & 0xFFFF) + 1:05x}{i:027x}" if trace else None
            row = self._post(req["prompt"], req["max_new_tokens"], trace_id)
            row["received"] = time.perf_counter()
            if trace and row["status"] == 200:
                row["ttft_ms"] = self._ttft_ms(trace_id)
            row["out_tokens"] = len(row.pop("tokens", []))
            return row

        before, compiles_before = self.engine_stats(), ctx.compiles()
        tracer = None
        if trace:
            tracer = threading.Thread(target=self._trace_slice, args=(seconds,), daemon=True)
            tracer.start()
        t0, records = pacer.run_open_loop(
            [r["due_s"] for r in requests], send, workers=int(traffic["client_threads"]))
        after = self.engine_stats()
        if tracer is not None:
            tracer.join()
        for rec, req in zip(records, requests):
            rec.update(prompt_len=len(req["prompt"]), max_new_tokens=req["max_new_tokens"])
        return {
            "t0": t0, "seconds": seconds, "records": records,
            "engine_before": before, "engine_after": after,
            "window_compiles": ctx.compiles() - compiles_before,
        }

    def _trace_slice(self, seconds: float) -> None:
        """Trace a few seconds in the middle of the window."""
        span = float(self.traffic.get("trace_seconds", 3.0))
        time.sleep(max(0.0, 0.5 * seconds - 0.5 * span))
        self.ctx.start_trace()
        time.sleep(span)
        self.reduced_trace = self.ctx.stop_trace()

    # -- correctness -------------------------------------------------------------

    def check(self) -> dict[str, Any]:
        """A few seeded requests through the same HTTP path, against the
        float32 reference's logits.

        Every emitted token's reference logit must lie within ``delta`` of
        that position's largest logit. Tokens, not argmax equality: with
        random weights the top two logits are often closer than bf16
        rounding. The reason for ``delta`` is in the configuration file."""
        spec = self.cfg["check"]
        rs = np.random.RandomState(self.ctx.seed + 7919)
        prompts = [rs.randint(0, self.vocab, int(spec["prompt_len"])).tolist()
                   for _ in range(int(spec["requests"]))]
        n_new = int(spec["new_tokens"])
        emitted = []
        for prompt in prompts:
            row = self._post(prompt, n_new, None)
            if row.get("status") != 200 or len(row["tokens"]) != n_new:
                return {"ok": False, "error": f"check request failed: {row}"}
            emitted.append(row["tokens"])
        params = self.engine().params  # the weights as served, on the device
        self.stop()  # frees the cache pool; the reference needs the room
        gc.collect()
        seqs = jnp.asarray([p + e[:-1] for p, e in zip(prompts, emitted)], jnp.int32)
        _, logits = self.ctx.reference.forward(params, seqs, **self.adapter.reference_args(self.cfg))
        at = logits[:, len(prompts[0]) - 1:]  # (requests, n_new, vocab): predicts each emitted token
        chosen = jnp.take_along_axis(at, jnp.asarray(emitted, jnp.int32)[..., None], axis=-1)[..., 0]
        gap = jnp.max(at, axis=-1) - chosen
        worst = float(jnp.max(gap))
        agree = float(jnp.mean(jnp.argmax(at, axis=-1) == jnp.asarray(emitted)))
        del params
        return {"ok": bool(worst <= float(spec["logit_delta"])), "worst_logit_gap": worst,
                "argmax_agreement": agree, "requests": len(prompts), "new_tokens": n_new,
                "logit_delta": float(spec["logit_delta"])}


def summarize(offered: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """Client records of one window to the numbers the metrics use."""
    records = offered["records"]
    t0 = offered["t0"]
    ok = [r for r in records if r.get("status") == 200 and r["out_tokens"] == r["max_new_tokens"]]
    failed = len(records) - len(ok)
    # a failed or unfinished request counts as beyond every percentile: it
    # is entered at the client's timeout
    timeout_ms = 1e3 * float(traffic["request_timeout_s"])
    ms_per_token = [1e3 * (r["received"] - r["due"]) / r["out_tokens"] for r in ok]
    ms_per_token += [timeout_ms] * failed
    out_tokens = sum(r["out_tokens"] for r in ok)
    last = max((r["received"] for r in ok), default=t0 + offered["seconds"])
    late_ms = [1e3 * (r["sent"] - r["due"]) for r in records]
    ttft = [r["ttft_ms"] + 1e3 * (r["sent"] - r["due"]) for r in ok if r.get("ttft_ms") is not None]
    return {
        "attempted": len(records), "failed": failed, "out_tokens": out_tokens,
        "makespan_s": last - t0,
        "ms_per_token": ms_per_token, "late_ms": late_ms, "ttft_ms": ttft,
        "offered_tokens_per_s": sum(r["max_new_tokens"] for r in records) / offered["seconds"],
    }


def end_to_end(summary: dict[str, Any]) -> dict[str, float]:
    return {
        "req_ms_per_token_p50": stats.percentile(summary["ms_per_token"], 0.50),
        "req_ms_per_token_p90": stats.percentile(summary["ms_per_token"], 0.90),
        "serve_out_tokens_per_s": summary["out_tokens"] / summary["makespan_s"],
    }


def run(ctx) -> dict[str, Any]:
    endpoint = Endpoint(ctx)
    endpoint.setup()
    setup_s = ctx.mark_setup_done()
    offered = endpoint.offer(ctx.seconds, ctx.seed, trace=ctx.trace)
    device = ctx.device_info()
    summary = summarize(offered, ctx.traffic)
    check = endpoint.check()  # stops the endpoint
    ctx.note(f"reference check: {json.dumps(check)}")
    before, after = offered["engine_before"], offered["engine_after"]
    module = ctx.config["module"]
    return {
        "setup_s": setup_s, "window_s": summary["makespan_s"],
        "attempted": summary["attempted"], "failed": summary["failed"],
        "correct": bool(check["ok"] and summary["failed"] == 0),
        "device": device,
        "end_to_end": end_to_end(summary),
        "counters": {
            "window_compiles": offered["window_compiles"],
            "engine_delta": {k: after[k] - before[k] for k in (
                "dispatches", "tokens_emitted", "prefill_chunks", "preemptions", "admission_waves")},
            "engine_after": after,
            "offered_tokens_per_s": summary["offered_tokens_per_s"],
            "out_tokens": summary["out_tokens"],
            "attention_shapes": {
                "num_layers": module["num_layers"], "num_heads": module["num_heads"],
                "num_kv_heads": module.get("num_kv_heads") or module["num_heads"],
                "d_head": module["d_model"] // module["num_heads"],
                "prefill_chunk": int(ctx.config["serving"]["lm_config"]["prefill_chunk"])},
        },
        "client": {
            "ms_per_token": summary["ms_per_token"], "late_ms": summary["late_ms"],
            "ttft_ms": summary["ttft_ms"], "check": check,
            "requests": [{k: r.get(k) for k in ("i", "prompt_len", "max_new_tokens", "status")}
                         | {"due_s": r["due"] - offered["t0"], "late_ms": 1e3 * (r["sent"] - r["due"]),
                            "latency_s": r["received"] - r["due"]} for r in offered["records"]],
        },
        "trace": endpoint.reduced_trace,
    }
