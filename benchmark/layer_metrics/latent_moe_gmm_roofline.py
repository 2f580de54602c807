"""The grouped matmuls' share of their roofline in a latent mixture of
experts, %: the least time the chip could take for the SIX grouped matmuls of
each routed layer and step (operations and bytes from
``kernels/latent_moe_gmm.py``: ``relu2`` experts at the latent's width, the
larger of the two roofs per matmul, costed on the rows and the experts the
chip holds) over the time the trace shows under the ``moe_experts`` scope,
which holds them and the activation between (over every chunk of routed rows
the layer worked on, held or not). A step whose check names no
``latent_moe_shapes`` (a SwiGLU layer at the model's width:
``moe_gmm_roofline``) gives None."""

from pathlib import Path

from benchmark.harness import loader, moe_scopes


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("latent_moe_shapes")
    if not shapes:
        return None
    experts_ms = moe_scopes.ms_per_step(run, bench_dir, ("moe_experts",))
    if not experts_ms:
        return None
    gmm = loader.load_module("kernels", "latent_moe_gmm", bench_dir)
    return 100.0 * 1e3 * gmm.least_seconds_per_step(shapes, run["device"]["kind"]) / experts_ms
