"""Every cell's set-up, window and comparison at a tiny size on the CPU,
and the comparison coming out false when the timed path is broken
underneath: a step that leaves the state unchanged, half of the work
left out, and an answer altered where it is produced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

CELLS = ["granite-3-2b.load", "ec4p2.write-1m", "ec4p2.randwrite-4k"]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct(run_tiny, cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 1 and res["failed"] == 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]
    assert all(c["limit"] == 0 for c in res["checks"].values())


def test_fio_touches_every_stripe_it_checks(run_tiny):
    res = run_tiny("ec4p2.write-1m", seconds=1.0)
    assert res["checks"]["stored_cells_differing"]["value"] == 0


# -- the load cell, broken underneath -----------------------------------------
def _load_fault(kind):
    from repro.core.device_direct import DeviceDirectSink
    real = DeviceDirectSink.read_tensors

    def broken(self, reqs, **kw):
        out = real(self, reqs, **kw)
        if kind == "unchanged":
            return [jnp.zeros_like(a) for a in out]
        if kind == "half":
            half = len(out) // 2
            return out[:half] + [jnp.zeros_like(a) for a in out[half:]]
        i = len(out) // 2                                  # "altered"
        flat = np.asarray(out[i]).reshape(-1).view(np.uint8).copy()
        flat[7] ^= 0x10
        out[i] = jax.device_put(flat.view(out[i].dtype)
                                .reshape(out[i].shape))
        return out
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_load_fault_is_not_correct(run_tiny, monkeypatch, kind):
    from repro.core.device_direct import DeviceDirectSink
    monkeypatch.setattr(DeviceDirectSink, "read_tensors", _load_fault(kind))
    res = run_tiny("granite-3-2b.load")
    assert not res["correct"]
    assert res["checks"]["tensor_bytes_differing"]["value"] > 0


# -- the EC cells, broken underneath ------------------------------------------
def _pwrite_unchanged(self, fd, data, offset):
    return len(data)


def _ec_writev_half(real):
    def broken(self, oid, offset, buffers):
        data = np.concatenate([np.frombuffer(bytes(b), np.uint8)
                               if not isinstance(b, np.ndarray) else b
                               for b in buffers])
        half = max(1, len(data) // 2)
        real(self, oid, offset, [data[:half]])
        return len(data)
    return broken


def _parity_altered(real):
    def broken(*args, **kwargs):
        out = np.array(real(*args, **kwargs), np.uint8)
        out[-1, 0] ^= 0x01
        return out
    return broken


@pytest.mark.parametrize("cell", ["ec4p2.write-1m", "ec4p2.randwrite-4k"])
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_ec_fault_is_not_correct(run_tiny, monkeypatch, cell, kind):
    from repro.core import client as cl
    from repro.kernels.rs_parity import ops as rs
    warm = {}
    if kind == "unchanged":
        # set-up writes go through; only the window's writes are no-ops
        real = cl.ROS2Client.pwrite
        monkeypatch.setattr(cl.ROS2Client, "pwrite", lambda self, fd, d, o:
                            (_pwrite_unchanged if warm.get("on") else real)
                            (self, fd, d, o))
    elif kind == "half":
        real = cl._ClusterRouter._ec_writev
        monkeypatch.setattr(cl._ClusterRouter, "_ec_writev",
                            lambda self, oid, off, bufs:
                            (_ec_writev_half(real) if warm.get("on")
                             else real)(self, oid, off, bufs))
    else:
        for name in ("ec_encode", "ec_parity_delta"):
            monkeypatch.setattr(rs, name, _parity_altered(getattr(rs, name)))
    from bench import harness
    real_measure = harness.measure

    def measure(driver, run, seconds, trace):
        warm["on"] = True
        real_measure(driver, run, seconds, trace)
    monkeypatch.setattr(harness, "measure", measure)
    res = run_tiny(cell)
    assert not res["correct"], res["checks"]


# -- the controls -------------------------------------------------------------
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(run_tiny, cell):
    from bench import controls
    spec_traffic = "load" if cell.endswith(".load") else "fio"
    undo = controls.install(controls.control_for({"driver": spec_traffic}))
    try:
        res = run_tiny(cell)
    finally:
        undo()
    assert not res["correct"]
    if spec_traffic == "load":
        assert res["checks"]["tensor_bytes_differing"]["value"] > 0
    else:
        # readback stays right: only the second parity cell is wrong
        assert res["checks"]["readback_bytes_differing"]["value"] == 0
        assert res["checks"]["stored_cells_differing"]["value"] > 0


# -- what the fio generator compares ------------------------------------------
def _fio_driver(tiny, cell, seed=41, **over):
    from bench.drivers import fio
    _spec, _cell, config, traffic = tiny(cell, **over)
    driver = fio.Driver(config, traffic, seed, None)
    driver.setup()
    return driver


def test_fio_file_starts_empty(tiny):
    """The file is made at its size with no data, as fio lays out a file
    for a write job: a range never written reads zeros."""
    d = _fio_driver(tiny, "ec4p2.randwrite-4k")
    try:
        for _ in range(5):
            d.step()
        size = d.client.stat("/fio.0")["size"]
        assert size == d.size
        got = np.frombuffer(d.client.pread(d.fd, d.stripe, 0), np.uint8)
        assert np.array_equal(got, d.expected(0, d.stripe))
        unwritten = np.flatnonzero(~d.touched)[0] * d.stripe
        assert not d.expected(unwritten, unwritten + d.stripe).any()
    finally:
        d.close()


def test_fio_sample_is_drawn_from_the_seed(tiny):
    picks = []
    for seed in (5, 5, 6):
        d = _fio_driver(tiny, "ec4p2.randwrite-4k", seed=seed,
                        check_stripes=2)
        try:
            for _ in range(20):
                d.step()
            s = d.sample()
            assert d.last_off // d.stripe in s and len(s) <= 3
            assert d.touched[s].all()
            picks.append(list(s))
        finally:
            d.close()
    assert picks[0] == picks[1]


def test_fio_expected_spans_writes_larger_than_a_stripe(tiny):
    d = _fio_driver(tiny, "ec4p2.write-1m", bs_bytes=2 << 20,
                    buffer_pool_bytes=4 << 20)
    try:
        d.step()
        want = d.pool[2 << 20:4 << 20]          # op 1 wrote buffer 1
        assert np.array_equal(d.expected(2 << 20, 4 << 20), want)
        assert d.touched[:4].all() and not d.touched[4:].any()
    finally:
        d.close()


def test_load_checkpoint_is_the_published_model(tiny):
    """At full depth the tensor list is granite-3.0-2b-base's; as run it
    is what the configuration file states."""
    from bench import harness
    from bench.drivers import load
    spec = harness.load_spec()
    cfg = harness.load_config(spec, "granite-3-2b-rp2")
    for depth, want in ((cfg["published"]["num_hidden_layers"],
                         cfg["published"]),
                        (cfg["num_hidden_layers"], cfg["as_run"])):
        specs = load.checkpoint_tensors(dict(cfg, num_hidden_layers=depth))
        n = sum(int(np.prod(s)) for _name, s in specs)
        assert (len(specs), n, 2 * n) == (want["tensors"],
                                          want["parameters"],
                                          want["bf16_bytes"])
