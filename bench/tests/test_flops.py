"""Operation and byte counts against hand counts, and the peak table."""
import math

import jax
import pytest

from bench.lib import flops
from bench.ref import federated

VGG11 = dict(name="vgg11_thinned", widths=[32, 64, 128, 128, 128, 128, 128,
                                            128],
             num_classes=10, in_channels=3, dense_width=128,
             pool_after=[0, 1, 3, 5, 7])


def test_vgg11_forward_matches_a_hand_count():
    # 3x3 convolutions at 32, 16, 8, 8, 4, 4, 2, 2 pixels a side (pooling
    # after layers 0, 1, 3, 5), then dense 128 -> 128 -> 10
    convs = [(32, 3, 32), (16, 32, 64), (8, 64, 128), (8, 128, 128),
             (4, 128, 128), (4, 128, 128), (2, 128, 128), (2, 128, 128)]
    macs = sum(side * side * cout * cin * 9 for side, cin, cout in convs)
    macs += 128 * 128 + 128 * 10
    init, apply = federated.load("models", "make_vgg").build(**VGG11)
    assert flops.forward_flops(init, apply, (32, 32, 3)) == 2 * macs


def test_round_flops_counts_algorithm_1():
    sizes = {"train": 100, "val": 10, "test": 50}
    # 96 of 100 rows are seen in batches of 32; 3F each for the epoch and
    # for each of 2 scale sub-epochs, F per validation row for 3 passes
    want = 4 * (3 * 7 * 96 * 3 + 7 * 10 * 3) + 7 * 50
    assert flops.round_flops(7, sizes, 4, 32, 2) == want


def test_vgg11_update_length():
    init, _ = federated.load("models", "make_vgg").build(**VGG11)
    params, _ = jax.eval_shape(init, jax.random.PRNGKey(0))
    shapes = [x.shape for x in jax.tree.leaves(params)]
    assert sum(math.prod(s) for s in shapes) == 849834


class _Ctx:
    """A traced run's context on a chip the peak table does not know."""

    def __init__(self, reduction, cell):
        from bench.lib.harness import MetricContext
        self._inner = MetricContext(reduction, cell, "TPU v99", 1)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_round_mfu_refuses_an_unknown_device_kind():
    from bench.lib import cell as cell_lib
    from bench.lib import trace as trace_lib
    from bench.lib.harness import _load_reader

    events = {"rounds": [["bench.round", 0, 1000]],
              "ops": {"0": [["delta_compress_batch.1", 100, 200],
                            ["fusion", 300, 900]]},
              "modules": {"0": []}}
    cell = cell_lib.load("vgg11-cifar10.sync-cabac")
    ctx = _Ctx(trace_lib.Reduction(events), cell)
    with pytest.raises(KeyError, match="TPU v99"):
        _load_reader("round_mfu").read(ctx)


def test_peaks_know_the_v5e():
    import json

    from bench.lib import cell as cell_lib
    peaks = json.loads((cell_lib.BENCH / "peaks.json").read_text())
    p = flops.peak(peaks, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
