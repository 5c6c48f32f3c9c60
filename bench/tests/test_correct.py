"""``correct`` at a size a test run can hold, on the CPU.

A run is driven as on the chip (``harness.run`` past the look for a chip)
on a cut-down copy of each cell; the cell's own limits decide.  A sound
program passes; the control (the reference computed in bfloat16 in the
program's place) and each fault a cell can have, planted in the program,
fail: a server step that leaves the state unchanged, half the cohort left
out of the aggregate with the mean taken over the rest, every update
altered where the wire produces it (decoded at twice its size), a server
step that leaves the filter scales unchanged, and one that leaves the
batch-norm statistics unfolded.  One chip has no exchange between chips
to leave out.
"""
import copy
import time

import jax.numpy as jnp
import pytest

from bench.lib import cell as cell_lib
from bench.lib import check, data, harness

CELLS = ["vgg11-cifar10.sync-cabac"]


def tiny(name):
    config, traffic = name.split(".")
    cell = copy.deepcopy(cell_lib.assemble(name, config, traffic))
    args = cell["config"]["model"]["args"]
    if cell["config"]["model"]["builder"] == "make_vgg":
        args.update(widths=[8, 16], dense_width=16, pool_after=[0, 1])
    else:
        args.update(widths=[8, 16], blocks_per_stage=1)
    cell["config"]["data"]["samples"] = 600
    cell["config"]["protocol"]["batch_size"] = 8
    cell["mix"]["clients"] = 4
    return cell


@pytest.fixture
def cpu_run(monkeypatch):
    """``harness.run`` past its look for a chip."""
    import jax
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())

    def run(cell, tmp_path, seed=2**32 + 3):
        return harness.run(cell, seed, 0.5, False, out_dir=tmp_path,
                           t0=time.perf_counter())
    return run


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name, tmp_path, cpu_run):
    res = cpu_run(tiny(name), tmp_path)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_is_not_correct(name):
    cell = tiny(name)
    import jax
    k_data, k_engine = jax.random.split(data.root_key(7))
    splits = data.make_splits(k_data, cell["config"]["data"],
                              cell["mix"]["clients"])
    ref = harness.reference_readings(cell, splits, k_engine)
    ctl = harness.reference_readings(cell, splits, k_engine,
                                     dtype=jnp.bfloat16)
    assert not check.verdict(check.compare(ctl, ref), cell["limits"])


def _unchanged(monkeypatch):
    from repro.fl import rounds
    monkeypatch.setattr(rounds.ServerStep, "__call__",
                        lambda self, server, agg, downlink, receivers,
                        transmit: (server, 0))


def _server_step_keeps(monkeypatch, field):
    from repro.fl import rounds
    step = rounds.ServerStep.__call__

    def call(self, server, agg, downlink, receivers, transmit):
        new, down = step(self, server, agg, downlink, receivers, transmit)
        return new._replace(**{field: getattr(server, field)}), down
    monkeypatch.setattr(rounds.ServerStep, "__call__", call)


def _scales_unchanged(monkeypatch):
    _server_step_keeps(monkeypatch, "scales")


def _bn_unfolded(monkeypatch):
    _server_step_keeps(monkeypatch, "bn_state")


def _half(monkeypatch):
    from repro.fl import rounds
    agg = rounds.Aggregate.__call__
    monkeypatch.setattr(rounds.Aggregate, "__call__",
                        lambda self, contribs, weights=None:
                        agg(self, contribs[:len(contribs) // 2], weights))


def _doubled(monkeypatch):
    import jax
    from repro.comms import codec
    dec = codec.Codec.decode

    def decode(self, payload, spec):
        d = dec(self, payload, spec)
        return d._replace(params=jax.tree.map(lambda x: 2 * x, d.params))
    monkeypatch.setattr(codec.Codec, "decode", decode)


@pytest.mark.parametrize("fault", [_unchanged, _half, _doubled,
                                   _scales_unchanged, _bn_unfolded],
                         ids=["state_unchanged", "half_cohort",
                              "update_altered", "scales_unchanged",
                              "bn_unfolded"])
@pytest.mark.parametrize("name", CELLS)
def test_faulty_program_is_not_correct(name, fault, tmp_path, monkeypatch,
                                       cpu_run):
    fault(monkeypatch)
    res = cpu_run(tiny(name), tmp_path)
    assert not res["correct"], res["checks"]
