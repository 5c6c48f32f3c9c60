"""Quickstart: 60 seconds of FSFL.

Runs a 2-client federated round-trip of the paper's pipeline on a small CNN
with synthetic CIFAR-like data, printing accuracy and EXACT DeepCABAC-coded
bytes per round for FedAvg vs FSFL.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core.fsfl import run_federated
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.launch.cache import enable_compile_cache
from repro.models import cnn


def main():
    enable_compile_cache()
    task = synthetic.ImageTask("quick", 10, 3, prototypes_per_class=2, noise=0.3)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 640)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y, num_clients=2)
    model = cnn.make_vgg("vgg_quick", [8, 16, 32], 10, 3, dense_width=16,
                         pool_after=(0, 1, 2))

    fedavg = ProtocolConfig(name="fedavg", method="none", quantize=False,
                            batch_size=32, local_lr=2e-3)
    fsfl = ProtocolConfig(name="fsfl", method="sparse", scaling=True,
                          error_feedback=True, fixed_sparsity=0.96,
                          structured=False, scale_lr=2e-2, scale_subepochs=2,
                          batch_size=32, local_lr=2e-3)

    print("=== FedAvg (uncompressed) ===")
    r1 = run_federated(model, fedavg, splits, rounds=5,
                       key=jax.random.PRNGKey(42), verbose=True)
    print("=== FSFL (ours: sparse + scaled + DeepCABAC) ===")
    r2 = run_federated(model, fsfl, splits, rounds=5,
                       key=jax.random.PRNGKey(42), verbose=True)

    b1, b2 = r1.records[-1].cum_bytes, r2.records[-1].cum_bytes
    print(f"\nFedAvg: acc={r1.final_acc:.3f}  total={b1/1e6:.2f} MB")
    print(f"FSFL:   acc={r2.final_acc:.3f}  total={b2/1e6:.4f} MB "
          f"({b1/b2:.0f}x less data)")


if __name__ == "__main__":
    main()
