"""Model zoo: TPU-first re-implementations of the reference's model set.

The reference's models live inside notebooks (MNIST CNN/FFN in
notebooks/ml/Experiment/*, ResNet-50 in notebooks/ml/Benchmarks/
benchmark.ipynb, wide-and-deep named by the TFX Chicago-Taxi config —
SURVEY.md §6). Here they are proper flax modules with bfloat16 compute
on the MXU and shared train-step factories.
"""

from hops_tpu import _startup

with _startup.importing("hops_tpu.models"):
    from hops_tpu.models import (  # noqa: F401
        common,
        differential_attention,
        generation,
        linear_attention,
        mnist,
        moe,
        resnet,
        state_space,
        transformer,
        widedeep,
    )
