"""Data+tensor-parallel ResNet-50 training over a device mesh — the
ParallelWrapper/SharedTrainingMaster replacement (SURVEY §2.3).

Run on 8 virtual devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/distributed_resnet.py
"""
import numpy as np
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.model.zoo import ResNet50
from deeplearning4j_tpu.parallel import DistributedTrainer, make_mesh


def main():
    mesh = make_mesh(data=-1)  # all devices, data-parallel
    model = ResNet50(num_classes=10, height=64, width=64, seed=7).init()
    trainer = DistributedTrainer(model, mesh=mesh)

    rng = np.random.RandomState(0)
    batch = 8 * mesh.shape["data"]
    x = rng.rand(batch, 3, 64, 64).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]
    for step in range(3):
        score = float(trainer.fit_batch(x, y))
        print(f"step {step}: loss={score:.4f} "
              f"(mesh={dict(mesh.shape)}, batch={batch})")


if __name__ == "__main__":
    main()
