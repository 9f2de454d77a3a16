"""Multi-host SPMD training: one T5 fine-tune whose mesh spans hosts.

The reference runs multi-node clusters through a managed platform
(flan-t5-batch-inference-job-setup.yml:2-3); the TPU-native shape is a
jax.distributed cluster where a trainer whose chip lease exceeds one host
routes its jitted step through the host-agent plane and every owning host
enters it in lockstep (docs/MULTIHOST.md).

This example emulates 2 hosts x 4 chips on one machine (the SURVEY §4.3
"multi-node without a cluster" technique); on a real pod the same code runs
with the TPU_AIR_COORDINATOR/TPU_AIR_NUM_PROCESSES env contract instead of
spawn_local_cluster.

Run:
    JAX_PLATFORMS=cpu python examples/multihost_training.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_air.parallel.distributed import spawn_local_cluster  # noqa: E402


def main() -> int:
    cluster = spawn_local_cluster(num_processes=2, devices_per_process=4)
    try:
        import numpy as np

        import tpu_air
        from tpu_air.data import from_items
        from tpu_air.models.t5 import T5Config
        from tpu_air.train import ScalingConfig, T5Trainer, TrainingArguments

        tpu_air.init()
        rng = np.random.default_rng(0)
        seq = 16
        rows = [
            {
                "input_ids": rng.integers(2, 250, size=seq).tolist(),
                "attention_mask": [1] * seq,
                "labels": rng.integers(2, 250, size=seq).tolist(),
            }
            for _ in range(32)
        ]
        trainer = T5Trainer(
            model_config=T5Config.tiny(),
            training_args=TrainingArguments(
                learning_rate=1e-4, per_device_train_batch_size=2,
                num_train_epochs=1,
            ),
            # 8 chips > 4 per host → the SPMD-multihost path: both hosts
            # enter the dp=4 x tp=2 step, gradients psum across hosts
            scaling_config=ScalingConfig(num_workers=4, model_parallel=2),
            datasets={"train": from_items(rows)},
        )
        result = trainer.fit()
        assert result.error is None, result.error
        m = result.metrics
        print(
            f"loss={m['loss']:.4f}  mesh=dp{m['mesh_data']}xtp{m['mesh_model']}"
            f"  hosts={m['mesh_num_hosts']}"
            f"  params/device={m['params_bytes_per_device']}"
            f"/{m['params_bytes_total']} bytes"
        )
        assert m["mesh_num_hosts"] == 2
        tpu_air.shutdown()
    finally:
        cluster.shutdown()
    print("MULTIHOST-EXAMPLE-OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
