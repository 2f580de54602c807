"""Span timers: wall-clock blocks feeding latency histograms.

``with span("hops_tpu_serving_request", model=name): ...`` times the
block into a ``<name>_seconds`` histogram in the global registry;
``@timed()`` does the same for whole functions. When the JAX profiler
is active (``runtime/diagnostics.trace``), each span additionally opens
a ``jax.profiler.TraceAnnotation`` so spans nest inside the XProf
timeline — one annotation vocabulary across metrics and traces.

:class:`StepTimer` is the step-loop shape of the same idea: one
``tick()`` per training step feeds the step-time histogram, the
steps/examples counters (PromQL ``rate()`` gives steps/sec and
examples/sec), and the ``hops_tpu_heartbeat_time`` gauge that
``runtime/preemption.py`` maintains and ``diagnostics.Watchdog`` can
watch.
"""

from __future__ import annotations

import contextlib
import functools
import re
import sys
import threading
import time
from typing import Any, Callable, Iterator

from hops_tpu import _startup
from hops_tpu.telemetry.metrics import DEFAULT_BUCKETS, REGISTRY, Registry
from hops_tpu.telemetry import tracing

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")

#: The well-known heartbeat gauge names (see module docstring). The
#: wall-clock gauge is for scrapes ("when did this loop last beat");
#: the monotonic twin is what in-process watchdogs compare against —
#: immune to NTP steps, meaningless across processes.
HEARTBEAT_GAUGE = "hops_tpu_heartbeat_time"
HEARTBEAT_MONO_GAUGE = "hops_tpu_heartbeat_monotonic"

#: The training step's device vocabulary: ``jax.named_scope`` names that
#: reach the device trace in every op's ``tf_op`` (forward and backward).
#: The first four are Flax module names the models already enter; the
#: last three are entered inside ``ops/xent.py``, the step factories and
#: ``parallel/grad_comms.py``. Readers outside the program (the
#: benchmark's ``harness/trace_scopes.py``) repeat these strings.
TRAIN_SCOPES = (
    "attn", "mlp", "embed", "final_norm",
    "lm_head_loss", "optimizer", "grad_exchange",
)
SCOPE_LM_HEAD_LOSS, SCOPE_OPTIMIZER, SCOPE_GRAD_EXCHANGE = TRAIN_SCOPES[4:]
SCOPE_EMBED = TRAIN_SCOPES[2]

#: Inside ``mlp``, the parts of a routed feed-forward (``models/moe.py``):
#: the router (matmul, softmax, top-k, the two auxiliary losses), the
#: dispatch (sort by expert, group sizes, gather), the experts (the three
#: grouped matmuls, forward and backward) and the combine (back to token
#: order, summed over a token's slots). A dense block enters none of them.
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
SCOPE_MLP = TRAIN_SCOPES[1]
#: Inside ``mlp`` beside them, the shared expert of a routed feed-forward
#: that has one (a dense SwiGLU every token passes).
SCOPE_MOE_SHARED = "moe_shared"

#: Inside ``attn``, the parts of a linear-attention (Gated DeltaNet) mixer
#: (``models/linear_attention.py``): the projections (q, k, v, the output
#: gate and the two per-head gates), the three short causal convolutions
#: with their activation, the scan (L2 norms, gates and the chunked gated
#: delta rule, forward and backward) and the output (gated norm and
#: ``W_o``). A softmax-attention block enters none of them.
LINATTN_SCOPES = ("linattn_proj", "linattn_conv", "linattn_scan", "linattn_out")
#: A Kimi-delta-attention mixer (``models/linear_attention.py``) enters the
#: same four, with ``ops/kda.py``'s rule in ``linattn_scan``. With the
#: published low-rank gate it enters ``linattn_gate`` beside them: the two
#: low-rank pairs (``f_b(f_a x)`` of the decay, ``g_b(g_a x)`` of the
#: channel-wise output gate), ``dt_bias``, the softplus and the rate; a
#: bounded full-rank layer computes its gate under ``linattn_proj``.
SCOPE_LINATTN_GATE = "linattn_gate"
#: Inside ``attn``, the output gate of a gated softmax-attention layer
#: (``models/transformer.py:Attention(output_gate=True)``): the projection
#: ``W_g x``, its sigmoid and the product with the heads' outputs.
SCOPE_ATTN_GATE = "attn_gate"

#: Inside ``attn``, the parts of a latent-attention mixer
#: (``models/transformer.py:LatentAttention``): the projections (``W_q``, the
#: low-rank key/value projection down and up with its norm, the head-wise
#: gate), the attention (per-head norms, the rotation of the rotary part, the
#: flash kernels with keys wider than values, forward and backward) and the
#: output (gate and ``W_o``). ``mtp`` is entered by the multi-token-prediction
#: module of a ``TransformerLM`` that has one (its two input norms and its
#: projection, which enter ``embed`` inside it; its block, whose parts enter
#: their own scopes inside it; and its final norm; the second chunked loss
#: runs under ``lm_head_loss`` like the first).
MLA_SCOPES = ("mla_proj", "mla_attn", "mla_out")
SCOPE_MTP = "mtp"

#: Inside ``attn``, the parts of a state-space (Mamba) mixer
#: (``models/state_space.py``): the projections (``W_in``, ``W_x``, the
#: step's ``W_dt`` and its softplus), the short causal convolution with its
#: activation, the selective scan (``ops/selective_scan.py``, forward and
#: backward; kernel names ``selective_scan_fwd``, ``selective_scan_bwd``)
#: and the gate (``y * silu(z)`` and ``W_out``). A gated memory unit enters
#: the first and the last. ``diff_attn`` is entered by an attention layer
#: of the differential form round its flash calls, the lambda combination
#: and the norm over a head pair's values: the projections stay outside.
SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")
SCOPE_DIFF_ATTN = "diff_attn"

#: The training step's host vocabulary: :func:`span` names entered by
#: ``Strategy.distribute_batch`` and by every ``Strategy.step`` callable,
#: children of the launcher's ``experiment.run`` root span.
SPAN_TRAIN_INPUT_PUT = "hops_tpu_train_input_put"
SPAN_TRAIN_DISPATCH = "hops_tpu_train_dispatch"

#: The start-up vocabulary: what a process spends between the kernel
#: starting it and its first training step, on the span ring's clock.
#: Readers outside the program (the benchmark's
#: ``harness/startup_spans.py``) repeat these strings.
#:
#: ``hops_tpu_compile`` is one JAX compile event, recorded with JAX's own
#: start and duration by the listener of ``runtime/compile_cache.py``:
#: ``phase`` = ``trace`` (Python to jaxpr) | ``lower`` (jaxpr to MLIR) |
#: ``backend`` (XLA compiling, or the persistent cache reading and
#: loading an executable), ``fun_name``, and on ``backend`` ``cache`` =
#: ``hit`` | ``miss`` | ``off``. A child of the calling context's active
#: span (a step's dispatch span, the launcher's root) or, without one,
#: of the process root. Every event feeds the histogram and the counter;
#: only one of ``COMPILE_SPAN_MIN_S`` or longer, or a cache miss, becomes
#: a span, so the eager operations of a set-up do not crowd a window's
#: dispatch spans out of the ring.
SPAN_PROCESS = tracing.PROCESS_ROOT
SPAN_COMPILE = "hops_tpu_compile"
HIST_COMPILE_SECONDS = "hops_tpu_compile_seconds"
COUNTER_COMPILES = "hops_tpu_compiles_total"
#: 0.5 ms: 340-860 spans a set-up, 0.14-0.37 s of events left out (PERF.md section 5).
COMPILE_SPAN_MIN_S = 0.0005
#: One import of a package that wraps its import block in
#: ``_startup.importing`` (``package``); a nested import is a child of
#: the import that caused it, the outermost of the process root.
SPAN_STARTUP_IMPORT = "hops_tpu_startup_import"
#: Process start to the first launcher entry of the process (recorded
#: then, once): the interpreter, every import, reaching the chip, the
#: caller's own loading. Under the process root.
SPAN_STARTUP_PRELAUNCH = "hops_tpu_startup_prelaunch"
#: Launcher entry to the call of the wrapper function, once a run: run
#: directory, registry record, log handler, strategy scope. Under the
#: run's ``experiment.run`` root.
SPAN_STARTUP_LAUNCH = "hops_tpu_startup_launch"
#: Process start to the return of the process's first ``Strategy.step``
#: call (the step is dispatched, not finished): what a start costs an
#: operator.
GAUGE_STARTUP_FIRST_STEP = "hops_tpu_startup_first_step_seconds"
#: Bytes of a train state's leaves by how ``Strategy.step``'s default path
#: lays them out across the data axis (``placement`` = ``split`` |
#: ``whole``), set when a step derives the layout of a state
#: (``parallel/strategy.py:Strategy.state_layout``): a device holds 1/n of
#: ``split`` and all of ``whole``. On a data axis of one device nothing is
#: derived and nothing is set.
GAUGE_TRAIN_STATE_BYTES = "hops_tpu_train_state_bytes"

#: Trace-time counters of the training vocabulary: each says which form of
#: an op a compiled step holds, and is added to while the step is traced.
#: ``hops_tpu_train_per_shard_traces_total{op}`` (``parallel/mesh.py``),
#: ``hops_tpu_train_loss_traces_total{pass}`` (``ops/xent.py``),
#: ``hops_tpu_train_moe_traces_total{impl, dispatch, weights}`` (``models/moe.py``),
#: ``hops_tpu_train_linattn_traces_total{impl}``
#: (``models/linear_attention.py``),
#: ``hops_tpu_train_ssm_traces_total{impl}`` (``models/state_space.py``),
#: ``hops_tpu_train_shared_reads_total{what="memory"|"kv"}``
#: (``models/transformer.py``, one per layer traced that reads a value
#: an earlier layer wrote) and
#: ``hops_tpu_train_layer_kinds_total{kind}`` (``models/transformer.py``,
#: one per layer traced) are named
#: where they are counted; the flash kernels' sub-tiles
#: (``ops/attention.py``: ``kernel`` = ``fwd`` | ``bwd``, ``kind`` =
#: ``interior`` | ``edge`` | ``skipped``, the counts of one batch-head per
#: traced call) are named here.
COUNTER_TRAIN_FLASH_SUBTILES = "hops_tpu_train_flash_subtiles_total"
#: One per traced call of a flash kernel, forward or backward, by the form its
#: keys came in (``ops/attention.py:_Keys``): ``whole`` (one array as wide as
#: the queries), ``two_part_shared`` (a part per head and ONE rotary part for
#: the heads of a batch row: latent attention in DeepSeek-V3's form) or
#: ``two_part_per_head`` (every head its own rotary part: Ling's, whose
#: per-head QK norm scales it by head).
COUNTER_TRAIN_FLASH_KEYS = "hops_tpu_train_flash_keys_total"
#: One per Mosaic call of the gated delta rule traced (``ops/gated_delta.py``:
#: ``kernel`` = ``gated_delta_local_fwd`` | ``gated_delta_fwd`` |
#: ``gated_delta_out_fwd`` | ``gated_delta_local_bwd`` | ``gated_delta_bwd``,
#: the ``pallas_call`` names): which parts of the rule a compiled step holds
#: in kernels. A step on the XLA route counts none.
COUNTER_TRAIN_LINATTN_KERNEL_CALLS = "hops_tpu_train_linattn_kernel_calls_total"
#: One per Mosaic call of the Kimi delta rule traced (``ops/kda.py``: ``kernel``
#: = ``kda_fwd`` | ``kda_bwd``, the ``pallas_call`` names); a step on the XLA
#: route counts none. ``hops_tpu_train_kda_traces_total{impl}``
#: (``models/linear_attention.py``) counts the layers traced by the route.
COUNTER_TRAIN_KDA_KERNEL_CALLS = "hops_tpu_train_kda_kernel_calls_total"
#: One per Kimi-delta-attention layer traced, by the form of its decay gate
#: (``models/linear_attention.py``): ``bound`` = the log-decay's lower bound
#: (``-5.0``: Ling's safe gate, kernels ``kda_fwd`` / ``kda_bwd``) | ``none``
#: (the published ``-exp(A_log) softplus(.)``, kernels ``kda_unbounded_fwd`` /
#: ``kda_unbounded_bwd``), ``rank`` = ``full`` | the rank of ``f_a`` / ``f_b``.
COUNTER_TRAIN_KDA_GATE = "hops_tpu_train_kda_gate_total"
#: One per token mixer traced that builds a share of its heads (``held_heads``
#: = (first, count): ``mixer`` = ``kimi_delta_attention`` | ``full_attention``,
#: ``held`` = the heads built, ``of`` = the layer's own count), beside the
#: ``moe_stats`` of a held share of experts.
COUNTER_TRAIN_HELD_HEADS = "hops_tpu_train_held_heads_total"

#: What ``TransformerLM(remat=True)`` keeps of a block's forward besides the
#: block's input: values that cost a kernel or a ``d_model``-wide matmul to
#: make again and are one ``d_model``-wide row a token (or less) to hold.
#: ``flash_out`` / ``flash_lse``: the flash forward's result and row
#: statistics, the backward kernels' operands (``ops/attention.py``);
#: ``mixer_out`` / ``mlp_out``: a sublayer's result
#: (``models/transformer.py:Block``). JAX holds a named value only where the
#: backward reads it: under a norm on each sublayer's output both, with the
#: norms in front ``mixer_out`` alone (the second norm reads the sum it
#: enters; ``Block`` does not name ``mlp_out`` there). Not kept, because they are several times the bytes per
#: millisecond and the two cells that run ``remat`` have no room for them:
#: the feed-forward's ``gate`` / ``up`` (FFN-wide), the linear-attention
#: layers' projections, the gated delta rule's float32 ``states``, ``W``,
#: ``U``, ``V'``, and the selective scan's ``y`` and chunk start states
#: (504 MB for 3.5 ms at the Phi-4-flash cell's widths: with them XLA's own
#: rematerialization ran two FFN-wide matmuls again to fit the chip, PERF.md
#: section 6, PR 32). ``kda_out`` / ``kda_states``: the Kimi delta rule's
#: result and the float32 state entering each chunk (``ops/kda.py:_rule_fwd``,
#: and nowhere else: only a Kimi-delta layer makes them), the exception to the
#: row-a-token rule that such a layer earns: its forward kernel runs at 7.7 %
#: of its roof, so the 335 MB a layer of the Ling cell buy 5.07 ms (66 MB a
#: ms) and the 84 MB of Solar-Open2's held heads 1.56 ms, where the selective
#: scan's pair cost 144 MB a ms, and both cells have the room (PERF.md
#: section 6, PR 48). ``router_logits`` / ``router_ids`` / ``moe_order`` /
#: ``moe_sizes``: what a routed layer decides (``models/moe.py``, and nowhere
#: else): the router's float32 logits ``(tokens, E)``, the chosen experts'
#: ids, the stable sort of the ``tokens x top_k`` rows (its inverse too, under
#: the same name, where every expert is held) and the rows per expert. With
#: them the second forward runs no router matmul, no ``top_k`` and no sort;
#: the weights are the scores at the kept ids (``moe._chosen``). Integers are kept
#: by name like any other value. 17.3 MB a layer of the Ling cell (512
#: experts) buy 1.41 ms, 11.0 MB of Solar-Open2's (320) 0.97 ms: 12 MB a ms,
#: the cheapest milliseconds ``remat`` had left (PERF.md section 6, PR 50).
REMAT_KEEPS = ("flash_out", "flash_lse", "mlp_out", "mixer_out", "kda_out", "kda_states",
               "router_logits", "router_ids", "moe_order", "moe_sizes", "ssd_out", "ssd_states")
#: One per value traced under a ``REMAT_KEEPS`` name (``what``), whether or
#: not a ``remat`` encloses it: outside one the name is the identity.
COUNTER_TRAIN_REMAT_KEPT = "hops_tpu_train_remat_kept_total"


_m_remat_kept = REGISTRY.counter(
    COUNTER_TRAIN_REMAT_KEPT, "Values traced under a name that remat keeps", labels=("what",))


def keep(x: Any, what: str) -> Any:
    """``x`` under the name ``what`` (one of ``REMAT_KEEPS``): the value a
    block's ``remat`` holds for the backward pass instead of computing it
    again. Lowers to nothing."""
    from jax.ad_checkpoint import checkpoint_name  # this module stays importable without jax

    if what not in REMAT_KEEPS:
        raise ValueError(f"{what!r} is not a name remat keeps (one of {REMAT_KEEPS})")
    _m_remat_kept.inc(what=what)
    return checkpoint_name(x, what)


# What follows stands below ``keep``, not beside the other vocabularies
# above it: a training step's compile-cache key holds the line ``keep``
# calls ``checkpoint_name`` from.

#: Of ``REMAT_KEEPS``, ``ssd_out`` / ``ssd_states``: the state-space-dual
#: scan's result and the float32 state entering each chunk
#: (``ops/ssd.py:_scan_fwd``, and nowhere else: only a Mamba-2 layer makes
#: them), kept by ``kda_out`` / ``kda_states``' rule: 16.8 + 33.5 MB a layer at
#: 16 heads x 8,192 tokens x (64, 128), so that the forward kernel runs once
#: a layer and step.
#:
#: A Mamba-2 mixer (``models/state_space.py:Mamba2``) enters ``SSM_SCOPES``
#: like a Mamba layer: ``ssm_proj`` (``W_in``, the step's bias and softplus,
#: the log-decay), ``ssm_conv`` (the convolution over ``[x | B | C]`` with its
#: activation), ``ssm_scan`` (``ops/ssd.py``, forward and backward; kernel
#: names ``ssd_fwd``, ``ssd_bwd``; the ``D x`` term) and ``ssm_gate`` (the
#: gate, the group-wise norm and ``W_out``).
#: ``hops_tpu_train_ssm_traces_total{impl}`` counts it as ``ssd_pallas`` |
#: ``ssd_xla_scan``. One per Mosaic call of the scan traced (``kernel`` =
#: ``ssd_fwd`` | ``ssd_bwd``, the ``pallas_call`` names):
COUNTER_TRAIN_SSD_KERNEL_CALLS = "hops_tpu_train_ssd_kernel_calls_total"
#: Inside ``mlp`` beside ``MOE_SCOPES``, the two shared projections of a
#: latent mixture of experts (``models/moe.py:MoEMLP(latent_dim=)``): ``W_down``
#: from the token's width to the experts' latent width before the dispatch and
#: ``W_up`` back after the combine. A layer whose experts read the token whole
#: never enters it.
SCOPE_MOE_LATENT = "moe_latent"
#: A looped model (``models/transformer.py:TransformerLM(loop_steps=T)``: the
#: layer stack and the final norm run ``T`` times on the same parameters, a
#: ``scan`` over the loop steps) enters ``loop_step`` round the scan's body, so
#: a device trace reads ``.../while/body/loop_step/block_0/attn/...``, and
#: ``loop_exit`` round what exits cost: the exit gate on each step's normed
#: hidden state (inside the body) and, in ``make_lm_train_step``, the exit
#: distribution the gates give and its entropy term (forward and backward).
SCOPE_LOOP_STEP = "loop_step"
SCOPE_LOOP_EXIT = "loop_exit"
#: One per trace of a looped model (``steps`` = its ``loop_steps``). The
#: scan's body is traced into the program ONCE whatever ``steps`` is;
#: ``hops_tpu_train_layer_kinds_total`` counts its layers once a trace.
COUNTER_TRAIN_LOOP_TRACES = "hops_tpu_train_loop_traces_total"


#: The serving engine's vocabulary (``modelrepo/lm_engine.py``,
#: ``modelrepo/serving.py:LMEnginePredictor``). Readers outside the program
#: (the benchmark's ``harness/engine_spans.py``) repeat these strings.
#:
#: ``hops_tpu_lm_iteration`` is one ``LMEngine.step()`` that had live work,
#: recorded as it ends with its wall-clock start and duration under the
#: engine's own root ``hops_tpu_lm_engine`` (``tracing.detached_root``: the
#: driver thread has no request context and an iteration serves many
#: requests). Attributes: ``seq`` (consecutive per engine), ``kind`` (what
#: it dispatched, ``+``-joined: ``chunk`` = prompt chunks alone, ``mixed`` =
#: chunks with decode rows fused in, ``decode``, ``horizon``, ``spec``,
#: ``spec_horizon``; the dense layout's admissions ``prefill``, ``append``),
#: ``dispatches``, one ``<phase>_ms`` per ``LM_PHASES`` member (they sum to
#: the duration less the few clock reads between them), ``rows_prefill``,
#: ``rows_decode``, ``tokens``, ``admitted``, ``preempted``, ``queued`` (the
#: queue's length as the iteration began), ``idle_before_ms`` (since the
#: previous ``step()`` returned), and ``error`` when the dispatch raised.
SPAN_LM_ENGINE = "hops_tpu_lm_engine"
SPAN_LM_ITERATION = "hops_tpu_lm_iteration"
#: An iteration's phases, in the order a paged iteration meets them:
#: ``admit`` (queue order, priority, slot and page-table bookkeeping),
#: ``blocks`` (page growth with its reclaim and preemption), ``build`` (host
#: lists to device operands), ``pages`` (page table and cache index pushed
#: to the device), ``dispatch`` (the jitted call until it returns; on a first
#: call its trace and compile), ``wait`` (the host blocked on the device for
#: the tokens), ``collect`` (per-token accounting, finishes, prefix
#: capture). Each is entered as a ``jax.profiler.TraceAnnotation``
#: ``hops_tpu_lm_<phase>`` and observed into
#: ``hops_tpu_lm_phase_seconds{phase}`` once an iteration.
LM_PHASES = ("admit", "blocks", "build", "pages", "dispatch", "wait", "collect")
LM_PHASE_ANNOTATION = "hops_tpu_lm_"  # + phase
HIST_LM_PHASE_SECONDS = "hops_tpu_lm_phase_seconds"
COUNTER_LM_ITERATIONS = "hops_tpu_lm_iterations_total"
#: One per ticket of a ``predict`` call, under the request's trace, from the
#: call's entry to its results: ``ticket``, ``tokens``, ``ttft_ms``, and what
#: the request waited for: ``lock_wait_ms`` (entry of ``predict`` to holding
#: the engine lock), ``queue_wait_ms`` (``submit`` to the start of the
#: iteration that first gave it a slot), ``first_iteration`` /
#: ``last_iteration`` (``seq`` of the iterations that admitted and finished
#: it), ``preemptions``, ``token_ms`` (offset from ``submit`` of the instant
#: the host got each token; ``token_ms[0]`` is ``ttft_ms``).
SPAN_LM_REQUEST = "lm_engine.dispatch"
HIST_LM_LOCK_WAIT = "hops_tpu_lm_lock_wait_seconds"
HIST_LM_QUEUE_WAIT = "hops_tpu_lm_queue_wait_seconds"
HIST_LM_INTER_TOKEN = "hops_tpu_lm_inter_token_seconds"


_happened: set[str] = set()  # guarded by: _happened_lock
_happened_lock = threading.Lock()


def first_in_process(what: str) -> bool:
    """True for the first caller in this process to ask about ``what``
    (``prelaunch``, ``first_step``), False ever after."""
    with _happened_lock:
        if what in _happened:
            return False
        _happened.add(what)
        return True


def _record_import(imp: _startup.importing) -> None:
    root = tracing.process_root()
    if root is None:
        return
    parent = root.context if imp.parent is None else tracing.TraceContext(
        root.trace_id, imp.parent.span_id, root.sampled)
    tracing.record_span(SPAN_STARTUP_IMPORT, parent, imp.start, imp.end - imp.start,
                        span_id=imp.span_id, package=imp.package)


# From here on an import goes to the ring as it ends; first, those that
# ended while this module was not yet importable.
_startup.sink = _record_import
while _startup.pending:
    _record_import(_startup.pending.pop(0))


def _sanitize(name: str) -> str:
    return _NAME_RE.sub("_", name)


def _histogram(name: str, labels: tuple[str, ...], registry: Registry):
    return registry.histogram(
        f"{_sanitize(name)}_seconds",
        f"Duration of {name} spans",
        labels=labels,
        buckets=DEFAULT_BUCKETS,
    )


@contextlib.contextmanager
def span(name: str, registry: Registry = REGISTRY,
         **labels: Any) -> Iterator[None]:
    """Time the block into ``<name>_seconds{**labels}``. Label NAMES
    must be consistent across uses of one span name (they declare the
    histogram's label set). Exceptions propagate but the duration is
    still recorded — error latency is latency.

    When the calling context carries an active distributed trace
    (``telemetry/tracing.py``), the block additionally records a child
    tracing span of the same name — one annotation vocabulary across
    metrics, XProf timelines, and request traces — and the histogram
    observation carries the trace id as an exemplar, so a latency
    bucket links back to a concrete trace."""
    hist = _histogram(name, tuple(sorted(labels)), registry)
    # Nest inside an active profiler trace without importing jax (and
    # dragging a backend up) from processes that never touched it.
    jax = sys.modules.get("jax")
    annotation = (
        jax.profiler.TraceAnnotation(name) if jax is not None
        else contextlib.nullcontext()
    )
    # Joins the active request trace; a no-op outside one (and the
    # whole lookup is one bool when tracing is disabled).
    tspan = tracing.child_span(name, **labels)
    start = time.monotonic()
    try:
        with annotation, tspan:
            yield
    finally:
        hist.observe(time.monotonic() - start,
                     exemplar=tracing.current_trace_id(), **labels)


def timed(name: str | None = None, registry: Registry = REGISTRY,
          **labels: Any) -> Callable:
    """Decorator form of :func:`span`; the metric name defaults to the
    function's qualified name (``hops_tpu_span_<module>_<fn>``)."""

    def deco(fn: Callable) -> Callable:
        span_name = name or _sanitize(
            f"hops_tpu_span_{fn.__module__}_{fn.__qualname__}"
        )

        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with span(span_name, registry=registry, **labels):
                return fn(*args, **kwargs)

        return wrapper

    return deco


class StepTimer:
    """Step-cadence telemetry for training/experiment loops.

    Call :meth:`tick` once per completed step (``examples=`` the batch
    size if known). Feeds, all labelled ``loop=<name>``:

    - ``hops_tpu_step_seconds`` — step-time histogram (time between
      consecutive ticks; the first tick only arms the clock),
    - ``hops_tpu_steps_total`` / ``hops_tpu_examples_total`` —
      counters whose scrape-side ``rate()`` is steps/sec and
      examples/sec,
    - ``hops_tpu_heartbeat_time`` — unix time of the last tick, the
      gauge ``diagnostics.Watchdog(watch_heartbeat_gauge=True)`` reads
      instead of requiring explicit ``heartbeat()`` calls.
    """

    def __init__(self, loop: str = "train", registry: Registry = REGISTRY):
        self.loop = loop
        self._step_seconds = registry.histogram(
            "hops_tpu_step_seconds", "Training step wall time",
            labels=("loop",),
        ).labels(loop=loop)
        self._steps = registry.counter(
            "hops_tpu_steps_total", "Training steps completed",
            labels=("loop",),
        ).labels(loop=loop)
        self._examples = registry.counter(
            "hops_tpu_examples_total", "Training examples consumed",
            labels=("loop",),
        ).labels(loop=loop)
        self._heartbeat = registry.gauge(
            HEARTBEAT_GAUGE,
            "Unix time of the last step-boundary heartbeat, per loop",
            labels=("loop",),
        ).labels(loop=loop)
        self._heartbeat_mono = registry.gauge(
            HEARTBEAT_MONO_GAUGE,
            "Monotonic clock of the last step-boundary heartbeat, per "
            "loop (for in-process watchdogs; not comparable across "
            "processes)",
            labels=("loop",),
        ).labels(loop=loop)
        self._last: float | None = None

    def _beat(self) -> None:
        self._heartbeat.set(time.time())
        self._heartbeat_mono.set(time.monotonic())

    def arm(self) -> None:
        """Reset the step clock without recording anything — call at a
        loop (re)start so the first tick doesn't measure idle time
        spanning two runs."""
        self._last = time.monotonic()
        self._beat()

    def tick(self, examples: int | None = None) -> None:
        now = time.monotonic()
        if self._last is not None:
            self._step_seconds.observe(now - self._last)
        self._last = now
        self._steps.inc()
        if examples:
            self._examples.inc(examples)
        self._beat()
