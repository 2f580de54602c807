"""Text-generation serving: train a toy LM → export → serve → generate.

The reference's serving story is one-shot classifier REST calls
(notebooks/ml/End_To_End_Pipeline/sklearn/
IrisClassification_And_Serving_SKLearn.ipynb, SURVEY.md §2.5); this
example runs the same export/create/start/infer lifecycle with the
framework's OWN model family: a ``TransformerLM`` trained on a cyclic
token pattern, exported with its next-token accuracy, and served
through the ``class Predict`` Python-predictor contract where each
request runs KV-cached ``generate()`` (Pallas decode path,
``eos_id`` termination). Training and the in-process serving host share
this one process, so both run on whatever device JAX finds.
"""

from __future__ import annotations

import json
import tempfile
from pathlib import Path

MODEL_NAME = "cycle_lm"

# Tokens 2..9 cycle; 0 is pad, 1 is eos (never seen in training data,
# so greedy decoding follows the cycle and never stops early).
VOCAB = 16
CYCLE = list(range(2, 10))

MODEL_CONFIG = dict(
    vocab_size=VOCAB, d_model=32, num_heads=2, num_layers=2,
    max_decode_len=64,
)

PREDICTOR_SCRIPT = '''
"""Python model server hosting KV-cached generation (contract:
reference iris_flower_classifier.py:1-27 — same class, generative model)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from flax import serialization

from hops_tpu.models.generation import generate
from hops_tpu.models.transformer import TransformerLM


class Predict:
    def __init__(self):
        d = Path(__file__).parent
        cfg = json.loads((d / "config.json").read_text())
        cfg["dtype"] = jnp.float32
        self.model = TransformerLM(**cfg)
        template = self.model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        self.params = serialization.from_bytes(
            template, (d / "params.msgpack").read_bytes()
        )

    def predict(self, instances):
        """instances: list of prompt token-id lists -> list of generated
        continuation token-id lists. Lengths MAY differ: with server-side
        batching the server coalesces prompts from different clients into
        one call, so prompts are grouped by length and each group runs
        one KV-cached pass (grouping, not padding — left-pad would shift
        a causal LM's positions)."""
        out = [None] * len(instances)
        by_len = {}
        for i, p in enumerate(instances):
            by_len.setdefault(len(p), []).append(i)
        for n, idxs in by_len.items():
            prompt = jnp.asarray([instances[i] for i in idxs], jnp.int32)
            gen = generate(
                self.model, self.params, prompt, jax.random.PRNGKey(0),
                max_new_tokens=16, temperature=0.0, eos_id=1, pad_id=0,
            )
            for row, i in enumerate(idxs):
                out[i] = gen[row, n:].tolist()
        return out
'''


def _train(steps: int = 60):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM, make_lm_train_step

    model = TransformerLM(dtype=jnp.float32, **MODEL_CONFIG)
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (8, 16),
        optimizer=optax.adam(3e-3), input_dtype=jnp.int32,
    )
    rs = np.random.RandomState(0)
    step = jax.jit(make_lm_train_step())
    cyc = np.array(CYCLE)
    for _ in range(steps):
        starts = rs.randint(0, len(CYCLE), size=(8,))
        tokens = np.stack([cyc[(s + np.arange(17)) % len(CYCLE)] for s in starts])
        state, metrics = step(state, {"tokens": jnp.asarray(tokens)})

    # Next-token accuracy on a held-out rotation of the cycle.
    eval_tokens = jnp.asarray([cyc[(3 + np.arange(17)) % len(CYCLE)]])
    logits = model.apply({"params": state.params}, eval_tokens[:, :-1])
    acc = float(jnp.mean(jnp.argmax(logits, -1) == eval_tokens[:, 1:]))
    return model, state.params, acc


def main() -> dict:
    from flax import serialization

    from hops_tpu.modelrepo import registry, serving

    model, params, acc = _train()

    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "params.msgpack").write_bytes(serialization.to_bytes(params))
        (Path(tmp) / "config.json").write_text(json.dumps(MODEL_CONFIG))
        (Path(tmp) / "predictor.py").write_text(PREDICTOR_SCRIPT)
        meta = registry.export(tmp, MODEL_NAME, metrics={"next_token_accuracy": acc})

    serving.create_or_update(
        MODEL_NAME, model_name=MODEL_NAME, model_version=meta["version"],
        model_server="PYTHON",
        # Concurrent clients coalesce into one predictor pass per window.
        batching_enabled=True, batching_config={"max_batch_size": 16,
                                                "timeout_ms": 10},
    )
    serving.start(MODEL_NAME)
    try:
        prompt = CYCLE[:4]
        resp = serving.make_inference_request(
            MODEL_NAME,
            {"signature_name": "serving_default", "instances": [prompt]},
        )
        continuation = resp["predictions"][0]

        # Concurrent clients with DIFFERENT prompt lengths: the server-
        # side batcher coalesces them; the predictor groups by length.
        import threading

        ragged = {}

        def client(key, p):
            ragged[key] = serving.make_inference_request(
                MODEL_NAME, {"instances": [p]})["predictions"][0]

        threads = [
            threading.Thread(target=client, args=("short", CYCLE[:2])),
            threading.Thread(target=client, args=("long", CYCLE[:6])),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        print(
            f"lm served: next-token acc={acc:.3f} prompt={prompt} "
            f"continuation={continuation} ragged_ok={sorted(ragged)}"
        )
        return {"accuracy": acc, "prompt": prompt, "continuation": continuation,
                "ragged": ragged}
    finally:
        serving.stop(MODEL_NAME)


if __name__ == "__main__":
    main()
