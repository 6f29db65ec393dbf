"""tpu-dml: a TPU-native (JAX/XLA) distributed machine-learning framework.

Provides the full capability surface of the Tsinghua "Distributed Machine
Learning" course lab suite (reference: Enigmatisms/
Distributed-Machine-Learning-Experiment-Document, see SURVEY.md), re-designed
TPU-first:

- ``tpudml.core``     — config, mesh/device discovery, distributed init, PRNG.
- ``tpudml.nn``       — functional (init/apply) neural-net module system incl.
                        multi-head attention (full/flash/ring/ulysses).
- ``tpudml.models``   — LeNet-style CNN, MLP, ResNet-18/34, staged split nets,
                        decoder-only TransformerLM.
- ``tpudml.optim``    — hand-written GD / SGD(+momentum) / Adam as pure pytree
                        transforms (reference: codes/task1/pytorch/MyOptimizer.py).
- ``tpudml.data``     — MNIST/CIFAR-10 loaders (IDX parser + synthetic
                        fallbacks), uint8-resident storage, sampler framework
                        (random partition / random sampling), per-host sharding.
- ``tpudml.comm``     — pytree collectives + aggregation strategies + comm stats.
- ``tpudml.parallel`` — DP (shard_map), GSPMD stage/tensor parallelism, GPipe
                        micro-batched pipeline, ring/Ulysses context parallelism.
- ``tpudml.ops``      — Pallas TPU kernels (fused attention).
- ``tpudml.native``   — C++ host data-plane (fused gather+dequantize, byteswap).
- ``tpudml.checkpoint`` — atomic pytree checkpoints + budget-based resume.
- ``tpudml.metrics``  — scalar writer (JSONL/TensorBoard), profiler
                        (reference: codes/datawriter.py).
- ``tpudml.launch``   — supervised multi-process launcher (compose replacement).
- ``tpudml.api``      — high-level Model(train/eval) facade (MindSpore-track).
"""

__version__ = "0.1.0"
