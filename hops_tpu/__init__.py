"""hops_tpu — a TPU-native ML-platform framework.

A ground-up re-design of the capabilities of the Hopsworks example suite
(``moritzmeister/hops-examples``, see SURVEY.md) for TPU hardware:

- ``hops_tpu.experiment`` — wrapper-function experiment launchers
  (``launch`` / ``mirrored`` / ``collective_all_reduce`` / ``grid_search`` /
  ``differential_evolution``), replacing Spark-executor launchers
  (reference: notebooks/ml/Experiment/*, SURVEY.md §2.3).
- ``hops_tpu.search`` — async parallel-trial driver (maggy-equivalent
  ``lagom``: Searchspace, reporter heartbeats, random search / ASHA,
  early stopping, LOCO ablation; reference: SURVEY.md §2.4).
- ``hops_tpu.runtime`` — slice topology discovery (``devices``), typed
  config, structured logging, run directories, filesystem façade
  (reference: hops.devices / hops.hdfs, SURVEY.md §2.2).
- ``hops_tpu.modelrepo`` — versioned model registry + serving + batch
  inference (reference: hops.model / hops.serving, SURVEY.md §2.5).
- ``hops_tpu.featurestore`` — feature-store layer: feature groups, lazy
  query algebra, time travel, training datasets, validation, tags
  (reference: hsfs, SURVEY.md §2.6).
- ``hops_tpu.jobs`` — jobs/orchestration API + DAG operators
  (reference: jobs-client/, airflow/, SURVEY.md §2.7).
- ``hops_tpu.telemetry`` — metrics registry, Prometheus ``/metrics``
  export, pubsub metric shipping, span timers (reference: the
  Kafka→ELK inference-log / Spark-executor-metrics pipeline,
  SURVEY.md §5).
- ``hops_tpu.parallel`` — meshes, shardings, collectives, ring attention.
- ``hops_tpu.ops`` — Pallas TPU kernels for hot ops.
- ``hops_tpu.models`` — model zoo (MNIST CNN/FFN, ResNet-50, wide&deep).

Distribution is SPMD over ``jax.sharding.Mesh`` with XLA collectives over
ICI/DCN — no Spark, no NCCL, no JVM.
"""

from hops_tpu import _startup  # first: the clock the rest of the import is timed on

__version__ = "0.1.0"

with _startup.importing("hops_tpu"):
    from hops_tpu.runtime import config, devices, fs, rundir  # noqa: F401

__all__ = [
    "__version__",
    "config",
    "devices",
    "fs",
    "rundir",
]
