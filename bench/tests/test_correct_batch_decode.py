"""``correct`` catches an altered update where the server decodes the
cohort's payloads together.

The gather intake decodes a round's payloads in one ``decode_batch``
call, so a fault planted only in the per-message ``Codec.decode`` never
reaches it.  This plants the same fault, every update decoded at twice
its size, in the batch decode as well as in the per-message one, checks
that it lands on every client's update, and that the cell's limits refuse
it.
"""
import pytest

from bench.tests.test_correct import CELLS, cpu_run, tiny  # noqa: F401


def _doubled_everywhere(monkeypatch):
    import jax
    from repro.comms import codec, codecs
    calls = []

    def doubled(d):
        return d._replace(params=jax.tree.map(lambda x: 2 * x, d.params))

    dec = codec.Codec.decode

    def decode(self, payload, spec):
        calls.append(1)
        return doubled(dec(self, payload, spec))
    monkeypatch.setattr(codec.Codec, "decode", decode)
    batch = codecs.NncCabacCodec.decode_batch

    def decode_batch(self, payloads, spec, *, clients=None):
        calls.append(len(payloads))
        with monkeypatch.context() as m:    # one doubling per message
            m.setattr(codec.Codec, "decode", dec)
            out = batch(self, payloads, spec, clients=clients)
        return [doubled(d) for d in out]
    monkeypatch.setattr(codecs.NncCabacCodec, "decode_batch", decode_batch)
    return calls


@pytest.mark.parametrize("name", CELLS)
def test_update_altered_in_the_batch_decode_is_not_correct(
        name, tmp_path, monkeypatch, cpu_run):  # noqa: F811
    calls = _doubled_everywhere(monkeypatch)
    cell = tiny(name)
    res = cpu_run(cell, tmp_path)
    assert sum(calls) >= cell["mix"]["clients"]
    assert not res["correct"], res["checks"]
