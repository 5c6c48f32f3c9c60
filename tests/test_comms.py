"""Behavioural contract of the comms subsystem (repro.comms): round-trip
correctness for every registered codec, byte-for-byte parity of the
nnc-cabac wire with the seed's measurement path, real-bitstream engine
rounds, channel-model timing/drops, and layer-selective payloads."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comms
from repro.core import fsfl as fsfl_lib
from repro.core import quant as quant_lib
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import Scenario, run_scenario
from repro.fl.engine import EngineConfig, encode_client_bytes, run_simulation
from repro.models import cnn

# lossy wire error bounds: fp16 = relative rounding, int8 = amax/254 per
# block (half a quantization step), both plus float slack
LOSSY_ATOL = {"fp16": lambda amax: 1e-6 + amax * 5e-4,
              "int8-blockscale": lambda amax: 1e-7 + amax / 250.0}


# ------------------------------------------------------------- fixtures

def _random_update(seed, ternary=False, shapes=None):
    """A consistent (levels, recon) update + spec on a small mixed tree."""
    rng = np.random.default_rng(seed)
    shapes = shapes or {"conv": {"w": (6, 4, 3, 3), "b": (6,)},
                        "fc": {"w": (5, 24)}}

    def tree_of(fn, node):
        if isinstance(node, dict):
            return {k: tree_of(fn, v) for k, v in node.items()}
        return fn(node)

    q = quant_lib.QuantConfig()
    params_t = tree_of(lambda s: jax.ShapeDtypeStruct(s, np.float32), shapes)
    fine = tree_of(lambda s: len(s) < 2, shapes)
    scales_shapes = {"s0": (6,), "s1": (5,)}
    scales_t = tree_of(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                       scales_shapes)

    if ternary:
        lv = tree_of(lambda s: rng.integers(-1, 2, s).astype(np.int32),
                     shapes)
        mags = tree_of(lambda s: np.float32(abs(rng.normal()) + 1e-3), shapes)
        recon = jax.tree.map(
            lambda l, m: (m * np.sign(l)).astype(np.float32), lv, mags)
    else:
        lv = tree_of(
            lambda s: (rng.integers(-40, 41, s)
                       * (rng.random(s) < 0.25)).astype(np.int32), shapes)
        recon = jax.tree.map(
            lambda l, f: l.astype(np.float32)
            * np.float32(q.fine_step_size if f else q.step_size), lv, fine)
    s_lv = tree_of(lambda s: rng.integers(-3, 4, s).astype(np.int32),
                   scales_shapes)
    s_recon = jax.tree.map(
        lambda l: l.astype(np.float32) * np.float32(q.fine_step_size), s_lv)

    spec = comms.WireSpec(params=params_t, scales=scales_t, fine_mask=fine,
                          step_size=q.step_size,
                          fine_step_size=q.fine_step_size, ternary=ternary)
    upd = comms.ClientUpdate(lv, s_lv, recon, s_recon)
    return upd, spec


def _assert_roundtrip(codec, upd, spec):
    payload = codec.encode(upd, spec)
    dec = codec.decode(payload, spec)
    for a, b in zip(jax.tree.leaves(upd.recon_params),
                    jax.tree.leaves(dec.params)):
        a = np.asarray(a)
        if codec.lossless:
            np.testing.assert_array_equal(a, b)
        else:
            amax = float(np.max(np.abs(a))) if a.size else 0.0
            np.testing.assert_allclose(a, b,
                                       atol=LOSSY_ATOL[codec.name](amax))
    if spec.scales is not None:
        for a, b in zip(jax.tree.leaves(upd.recon_scales),
                        jax.tree.leaves(dec.scales)):
            # every codec keeps the scales section float32-exact or fine-step
            # lossless: fp16/int8 transmit them raw fp32 by design
            np.testing.assert_array_equal(np.asarray(a), b)
    return payload


# ------------------------------------------------------------- registry

def test_registry_has_the_paper_stack_and_at_least_five_codecs():
    names = comms.list_codecs()
    assert len(names) >= 5
    assert {"raw-fp32", "fp16", "int8-blockscale", "golomb",
            "nnc-cabac"} <= set(names)
    # auto resolution: seed semantics (quantizing -> cabac, raw otherwise)
    assert comms.resolve_codec("auto", quantize=True).name == "nnc-cabac"
    assert comms.resolve_codec("auto", quantize=False).name == "raw-fp32"
    with pytest.raises(KeyError):
        comms.get_codec("no-such-codec")


@pytest.mark.parametrize("name", ["raw-fp32", "fp16", "int8-blockscale",
                                  "golomb", "nnc-cabac"])
def test_codec_roundtrip_deterministic(name):
    codec = comms.get_codec(name)
    for seed in range(3):
        upd, spec = _random_update(seed)
        _assert_roundtrip(codec, upd, spec)


@pytest.mark.parametrize("name", ["raw-fp32", "fp16", "int8-blockscale",
                                  "golomb", "nnc-cabac"])
def test_codec_roundtrip_ternary(name):
    codec = comms.get_codec(name)
    upd, spec = _random_update(11, ternary=True)
    _assert_roundtrip(codec, upd, spec)


@pytest.mark.parametrize("name", ["raw-fp32", "golomb", "nnc-cabac"])
def test_send_mask_drops_leaves_from_wire(name):
    codec = comms.get_codec(name)
    upd, spec = _random_update(5)
    full = codec.encode(upd, spec)
    mask = {"conv": {"w": False, "b": False}, "fc": {"w": True}}
    spec_m = dataclasses.replace(spec, send_mask=mask)
    partial = codec.encode(upd, spec_m)
    assert len(partial) < len(full)
    dec = codec.decode(partial, spec_m)
    np.testing.assert_array_equal(dec.params["conv"]["w"], 0.0)
    np.testing.assert_array_equal(dec.params["fc"]["w"],
                                  np.asarray(upd.recon_params["fc"]["w"]))


# ------------------------------------------------------------- parity

def test_nnc_cabac_payload_length_equals_seed_accounting():
    """The wire payload IS the seed's measurement: identical byte counts."""
    codec = comms.get_codec("nnc-cabac")
    for seed, ternary in [(0, False), (1, False), (2, True)]:
        upd, spec = _random_update(seed, ternary=ternary)
        payload = codec.encode(upd, spec)
        assert len(payload) == encode_client_bytes(
            upd.levels_params, upd.levels_scales, ternary=ternary)


# ------------------------------------------------------------- device encode

def _stack_round_output(upds):
    """Fake the stacked RoundOutput trees encode_cohort reads (device
    arrays on the leading client axis, like fl/executors' vmap output)."""
    from types import SimpleNamespace

    def stack(*xs):
        return jnp.stack([jnp.asarray(x) for x in xs])

    return SimpleNamespace(
        levels_params=jax.tree.map(stack, *[u.levels_params for u in upds]),
        levels_scales=jax.tree.map(stack, *[u.levels_scales for u in upds]),
        recon_delta_params=jax.tree.map(
            stack, *[u.recon_params for u in upds]),
        recon_delta_scales=jax.tree.map(
            stack, *[u.recon_scales for u in upds]),
        bn_state=(jax.tree.map(stack, *[u.bn for u in upds])
                  if upds[0].bn is not None else None))


def _with_bn(upd, spec, seed):
    rng = np.random.default_rng(seed + 900)
    bn_shapes = {"bn0": {"mean": (6,), "var": (6,)}}
    bn_t = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                        bn_shapes, is_leaf=lambda x: isinstance(x, tuple))
    bn = jax.tree.map(lambda t: rng.normal(size=t.shape).astype(np.float32),
                      bn_t)
    return (upd._replace(bn=bn),
            dataclasses.replace(spec, bn=bn_t, version=2))


@pytest.mark.parametrize("name", ["int8-blockscale", "golomb", "nnc-cabac"])
@pytest.mark.parametrize("schema", [1, 2])
@pytest.mark.parametrize("ternary", [False, True])
def test_encode_cohort_byte_equal_to_host(name, schema, ternary):
    """The device cohort encode must produce BYTE-IDENTICAL payloads to
    the host encode_batch for every codec x wire schema x ternary combo —
    device_encode is a dispatch-count optimisation, never a bytes change."""
    codec = comms.get_codec(name)
    K = 4
    upds, spec = [], None
    for i in range(K):
        u, spec = _random_update(50 * i + schema, ternary=ternary)
        if schema == 2:
            u, spec = _with_bn(u, spec, 50 * i)
        upds.append(u)
    out = _stack_round_output(upds)
    host = codec.encode_batch(upds, spec, clients=list(range(K)))
    dev = codec.encode_cohort(out, spec, clients=list(range(K)))
    assert dev is not None
    assert [bytes(p) for p in dev] == [bytes(p) for p in host]
    # and every payload still decodes through the unmodified host decoder
    decs = codec.decode_batch(dev, spec, clients=list(range(K)))
    for u, d in zip(upds, decs):
        for a, b in zip(jax.tree.leaves(u.recon_params),
                        jax.tree.leaves(d.params)):
            if codec.lossless:
                np.testing.assert_array_equal(np.asarray(a), b)


def test_encode_cohort_base_returns_none():
    """Codecs without a device fast path fall back (None => host encode);
    the cohort contract is still validated."""
    codec = comms.get_codec("raw-fp32")
    upds = [_random_update(i)[0] for i in range(3)]
    spec = _random_update(0)[1]
    out = _stack_round_output(upds)
    assert codec.encode_cohort(out, spec, clients=[0, 1, 2]) is None
    with pytest.raises(ValueError, match="duplicate"):
        codec.encode_cohort(out, spec, clients=[0, 1, 1])


def test_encode_cohort_counts_one_dispatch_per_cohort():
    """The K x leaves -> O(1) collapse: one fused program per cohort,
    independent of K."""
    from repro.comms import device as comms_device
    codec = comms.get_codec("int8-blockscale")
    for K in (2, 8):
        upds = [_random_update(i)[0] for i in range(K)]
        spec = _random_update(0)[1]
        out = _stack_round_output(upds)
        before = comms_device.dispatch_count()
        codec.encode_cohort(out, spec, clients=list(range(K)))
        assert comms_device.dispatch_count() - before == 1


def test_golomb_device_zigzag_boundary_takes_host_fallback():
    """int32 zigzag (``buf << 1 ^ buf >> 31``) overflows at
    ``|level| >= 2**30``, so the device program's range guard must reject
    EXACTLY the boundary magnitude (host fallback — bytes unchanged by
    construction) while ``2**30 - 1`` stays on the byte-identical device
    path.  An off-by-one here silently corrupts the stream for the
    largest representable levels."""
    from repro.comms.device import _ZIGZAG_SAFE

    codec = comms.get_codec("golomb")

    def cohort_with(mag):
        upds, spec = [], None
        for i in range(2):
            u, spec = _random_update(50 * i + 1)
            upds.append(u)
        lv = jax.tree.map(np.copy, upds[0].levels_params)
        lv["conv"]["b"][0] = mag
        lv["conv"]["b"][1] = -mag
        upds[0] = upds[0]._replace(levels_params=lv)
        return upds, spec

    # one inside the guard: device path runs and matches the host bytes
    upds, spec = cohort_with(_ZIGZAG_SAFE - 1)
    dev = codec.encode_cohort(_stack_round_output(upds), spec,
                              clients=[0, 1])
    host = codec.encode_batch(upds, spec, clients=[0, 1])
    assert dev is not None
    assert [bytes(p) for p in dev] == [bytes(p) for p in host]

    # exactly at the boundary: strict guard -> None -> the caller's host
    # fallback, which still encodes and decodes the cohort fine
    upds, spec = cohort_with(_ZIGZAG_SAFE)
    assert codec.encode_cohort(_stack_round_output(upds), spec,
                               clients=[0, 1]) is None
    host = codec.encode_batch(upds, spec, clients=[0, 1])
    assert len(codec.decode_batch(host, spec, clients=[0, 1])) == 2


def test_int8_encode_body_single_dispatch_per_message():
    """Satellite: the host encode concatenates all sent leaves into one
    padded buffer — ONE kernel dispatch per message, not one per leaf
    (payload layout unchanged, asserted byte-for-byte elsewhere)."""
    import unittest.mock as mock

    codec = comms.get_codec("int8-blockscale")
    upd, spec = _random_update(3)
    kern = codec._kernel()
    with mock.patch.object(type(codec), "_kernel",
                           return_value=mock.Mock(wraps=kern)) as mk:
        codec.encode(upd, spec)
    # _kernel() itself may be consulted once; the kernel RUNS once
    assert mk.return_value.call_count == 1


# hypothesis property tests (dev extra; plain tests above cover the container)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False

if HAVE_HYP:
    @given(st.sampled_from(["raw-fp32", "fp16", "int8-blockscale", "golomb",
                            "nnc-cabac"]),
           st.integers(1, 20), st.integers(1, 16), st.floats(0.0, 1.0),
           st.integers(0, 2**31 - 1), st.booleans())
    @settings(max_examples=60, deadline=None)
    def test_codec_roundtrip_property(name, m, n, density, seed, ternary):
        rng = np.random.default_rng(seed)
        q = quant_lib.QuantConfig()
        shape = (m, n)
        if ternary:
            lv = rng.integers(-1, 2, shape).astype(np.int32)
            mag = np.float32(abs(rng.normal()) + 1e-3)
            recon = (mag * np.sign(lv)).astype(np.float32)
        else:
            lv = (rng.integers(-(2**16), 2**16, shape)
                  * (rng.random(shape) < density)).astype(np.int32)
            recon = lv.astype(np.float32) * np.float32(q.step_size)
        spec = comms.WireSpec(
            params={"w": jax.ShapeDtypeStruct(shape, np.float32)},
            scales=None, fine_mask={"w": False},
            step_size=q.step_size, fine_step_size=q.fine_step_size,
            ternary=ternary)
        upd = comms.ClientUpdate({"w": lv}, None, {"w": recon}, None)
        codec = comms.get_codec(name)
        payload = codec.encode(upd, spec)
        dec = codec.decode(payload, spec)
        if codec.lossless:
            np.testing.assert_array_equal(dec.params["w"], recon)
        else:
            amax = float(np.max(np.abs(recon))) if recon.size else 0.0
            np.testing.assert_allclose(dec.params["w"], recon,
                                       atol=max(amax / 250.0, 1e-7))


# ------------------------------------------------------------- channel

def test_channel_times_deterministic_and_monotone_in_bytes():
    cfg = comms.ChannelConfig(up_mbps=1.0, down_mbps=8.0, latency_s=0.1,
                              bandwidth_sigma=0.5, seed=4)
    a = comms.ChannelModel(cfg, 6)
    b = comms.ChannelModel(cfg, 6)
    for c in range(6):
        assert a.up_time(c, 1000) == b.up_time(c, 1000)
        assert a.up_time(c, 2000) > a.up_time(c, 1000) > 0.1
        assert a.down_time(c, 1000) < a.up_time(c, 1000)  # 8x faster down
    # infinite bandwidth -> latency only
    free = comms.ChannelModel(comms.ChannelConfig(latency_s=0.2), 2)
    assert free.up_time(0, 10**9) == pytest.approx(0.2)
    # drops deterministic per (round, client)
    lossy = comms.ChannelModel(comms.ChannelConfig(drop_rate=0.5, seed=1), 4)
    draws = [(t, c, lossy.dropped(t, c)) for t in range(4) for c in range(4)]
    assert draws == [(t, c, lossy.dropped(t, c)) for t in range(4)
                     for c in range(4)]
    assert any(d for _, _, d in draws) and not all(d for _, _, d in draws)


# ------------------------------------------------------------- end to end

def _tiny_setting(num_clients):
    task = synthetic.ImageTask("t", num_classes=4, channels=3, size=32,
                               prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=num_clients)
    model = cnn.make_vgg("vgg_tiny_comms", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    return model, splits


@pytest.fixture(scope="module")
def tiny2():
    return _tiny_setting(2)


def test_wire_round_reproduces_seed_byte_pin(tiny2):
    """Regression pin: the nnc-cabac wire path reproduces the seed's
    `measure_update_bytes` totals AND accuracies (captured from the seed
    engine before the wire refactor)."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    res = fsfl_lib.run_federated(model, cfg, splits, 2, jax.random.PRNGKey(7))
    assert [r.up_bytes for r in res.records] == [727, 712]
    assert [round(r.test_acc, 6) for r in res.records] == [0.166667, 0.208333]

    cfg_t = ProtocolConfig(name="stc", method="ternary", error_feedback=True,
                           fixed_sparsity=0.9, structured=False,
                           batch_size=32, local_lr=2e-3)
    res_t = fsfl_lib.run_federated(model, cfg_t, splits, 2,
                                   jax.random.PRNGKey(7))
    assert [r.up_bytes for r in res_t.records] == [561, 566]


def test_wire_is_transparent_for_level_lossless_codecs(tiny2):
    """Transmitting real bitstreams must not change fsfl numerics: the
    decoded reconstruction is bit-identical to the device-side dequantize,
    so accuracies match the no-wire fast path."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    wired = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                           engine=EngineConfig(measure_bytes=True))
    fast = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                          engine=EngineConfig(measure_bytes=False))
    for a, b in zip(wired.records, fast.records):
        assert a.test_acc == b.test_acc
        assert b.up_bytes == 0 and a.up_bytes > 0


def test_codec_axis_bytes_ordering(tiny2):
    """One engine round per codec family: every payload decodes and the
    ladder ordering holds (cabac < golomb < raw).  The full five-codec
    ladder runs in benchmarks/compression.py --smoke (CI)."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    sizes = {}
    for name in ["nnc-cabac", "golomb", "raw-fp32"]:
        res = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                             engine=EngineConfig(codec=name))
        sizes[name] = res.records[0].up_bytes
        assert sizes[name] > 0
    assert sizes["nnc-cabac"] < sizes["golomb"] < sizes["raw-fp32"]


def test_channel_converts_bytes_to_round_time(tiny2):
    """Compression ratio becomes wall-clock: raw fp32 rounds take longer
    than DeepCABAC rounds on the same constrained channel."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    chan = comms.ChannelConfig(up_mbps=1.0, down_mbps=8.0, latency_s=0.05)
    cabac = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                           engine=EngineConfig(channel=chan))
    raw = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(channel=chan, codec="raw-fp32"))
    assert 0.0 < cabac.records[0].sim_time_s < cabac.records[1].sim_time_s
    assert raw.records[-1].sim_time_s > cabac.records[-1].sim_time_s
    # without a channel the sync clock stays at zero
    off = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                         engine=EngineConfig())
    assert off.records[0].sim_time_s == 0.0


def test_channel_drops_exclude_clients_but_charge_bytes(tiny2):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    chan = comms.ChannelConfig(drop_rate=0.5, seed=3)
    res = run_simulation(model, cfg, splits, 3, jax.random.PRNGKey(7),
                         engine=EngineConfig(channel=chan))
    parts = [r.participants for r in res.records]
    assert any(len(p) < 2 for p in parts)       # someone dropped
    assert all(r.up_bytes > 0 for r in res.records)  # uploads still charged


def test_total_drop_stalls_server_but_residual_retransmits(tiny2):
    """drop_rate=1.0 + error feedback: no aggregation ever happens (server
    frozen, empty participants), yet clients keep re-carrying the lost mass
    so later payloads grow rather than vanish (Eq. 5 across drops)."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         error_feedback=True, batch_size=32, local_lr=2e-3)
    chan = comms.ChannelConfig(drop_rate=1.0)
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(channel=chan))
    assert all(r.participants == () for r in res.records)
    assert res.records[0].test_acc == res.records[1].test_acc  # server frozen
    assert all(r.up_bytes > 0 for r in res.records)
    # the re-injected residual makes round 2 carry round 1's mass on top of
    # fresh training: the coded payload grows
    assert res.records[1].up_bytes > res.records[0].up_bytes


def test_channel_requires_wire(tiny2):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", batch_size=32)
    with pytest.raises(ValueError, match="measure_bytes"):
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(0),
                       engine=EngineConfig(
                           channel=comms.ChannelConfig(up_mbps=1.0),
                           measure_bytes=False))


def test_async_rejects_drop_rate(tiny2):
    """Drops are modeled for sync rounds only — async must refuse them
    rather than silently ignoring drop_rate."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", batch_size=32)
    with pytest.raises(ValueError, match="drop"):
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(0),
                       engine=EngineConfig(
                           mode="async",
                           channel=comms.ChannelConfig(drop_rate=0.2)))


def test_level_codec_rejects_unquantized_protocol(tiny2):
    """A level codec on a quantize=False protocol would break Eq. 5 (wire
    loss never enters the residual) — must be refused, like 'auto' avoids."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="eq23_fp", method="sparse", quantize=False,
                         error_feedback=True, batch_size=32)
    with pytest.raises(ValueError, match="quantize=False"):
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(0),
                       engine=EngineConfig(codec="golomb"))


def test_partial_updates_shrink_wire_payloads(tiny2):
    """Layer-selective payloads: the fc-only predicate drops conv leaves
    from the wire, so partial rounds cost fewer bytes than full rounds."""
    model, splits = tiny2
    proto = dict(method="sparse", fixed_sparsity=0.5, batch_size=32,
                 local_lr=2e-3)
    cfg_partial = ProtocolConfig(
        name="partial", trainable_predicate=lambda p, l: p.startswith("fc"),
        **proto)
    cfg_full = ProtocolConfig(name="full", **proto)
    pred = lambda path, leaf: path.startswith("fc")
    part = run_simulation(model, cfg_partial, splits, 1, jax.random.PRNGKey(7),
                          engine=EngineConfig(up_predicate=pred))
    full = run_simulation(model, cfg_full, splits, 1, jax.random.PRNGKey(7))
    assert 0 < part.records[0].up_bytes < full.records[0].up_bytes


def test_noniid_scenarios_registered_and_heterogeneous():
    """ROADMAP satellite: dirichlet scenarios exist, cross two codecs, and
    actually produce label-skewed client splits."""
    from repro.fl import get_scenario, list_scenarios
    names = list_scenarios()
    assert {"noniid_dir01_fsfl", "noniid_dir01_golomb",
            "noniid_dir01_fp16", "noniid_dir1_k4_fedyogi"} <= set(names)
    assert get_scenario("noniid_dir01_golomb").codec == "golomb"
    assert get_scenario("noniid_dir01_fp16").codec == "fp16"
    from repro.fl.scenarios import default_setting
    _, nid = default_setting(4, dirichlet_alpha=0.1)
    _, iid = default_setting(4)

    def skew(splits):
        return float(np.mean([
            (np.bincount(np.asarray(splits.client_y[c]), minlength=10)
             / splits.client_y.shape[1]).max()
            for c in range(splits.num_clients)]))

    assert skew(nid) > skew(iid) + 0.1


def test_noniid_codec_scenario_runs_end_to_end():
    res = run_scenario("noniid_dir01_golomb", rounds=1)
    assert res.records[0].up_bytes > 0


# ------------------------------------------------------------- dist gating

def test_every_repro_module_imports_without_mesh_runtime():
    """Importing ANY repro module (including the revived `repro.dist`
    FL multi-host runtime) must work on a plain single-process checkout —
    no module may touch the coordination service at import time, and
    `require_dist()` returns the runtime instead of exiting."""
    import importlib
    import os
    import pkgutil

    import repro

    saved = os.environ.get("XLA_FLAGS")
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        importlib.import_module(mod.name)
    assert os.environ.get("XLA_FLAGS") == saved, \
        "importing a module must not set XLA_FLAGS"

    import repro.dist
    from repro.launch import require_dist
    assert require_dist() is repro.dist
