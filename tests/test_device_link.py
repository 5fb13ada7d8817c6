"""Link-aware backend routing policy (utils/device_link.py).

The router itself is exercised against synthetic link measurements — the
policy must hold regardless of what hardware the test box has. Reference
contrast: the reference pins engine work to CPU threads (no accelerator
placement exists there); this router is the TPU-native design's answer to
heterogeneous host↔accelerator attach topologies."""

from zeebe_tpu.utils.device_link import BackendRouter


class _Dev:
    def __init__(self, platform):
        self.platform = platform


def make_router(put_s, get_s):
    r = BackendRouter()
    r._measured = True
    r._accel = _Dev("tpu")
    r._host = _Dev("cpu")
    r.enabled = True
    r.link_put_s = put_s
    r.link_get_s = get_s
    return r


def test_slow_link_routes_to_host():
    r = make_router(put_s=0.07, get_s=0.07)  # a link with a 70 ms floor
    bucket = ("t", 2048, 2048)
    # unseated host model: trial run on host
    assert r.choose(bucket) is r._host
    r.record(bucket, r._host, 0.020)
    # seated: 140ms predicted link cost (one upload, one fetch a group)
    # never beats a 20ms host group
    assert r.choose(bucket) is r._host


def test_fast_link_routes_to_accel():
    r = make_router(put_s=50e-6, get_s=50e-6)  # PCIe-grade link
    bucket = ("t", 2048, 2048)
    # predicted link cost (0.1ms) is under the local threshold: the
    # accelerator wins even before the host model is seated
    assert r.choose(bucket) is r._accel


def test_fast_link_but_faster_host_switches_back():
    r = make_router(put_s=1500e-6, get_s=1500e-6)
    bucket = ("t", 64, 64)
    r.record(bucket, r._host, 0.001)
    r.record(bucket, r._host, 0.001)
    # a 3ms link beats nothing when the host does the group in 1ms
    assert r.choose(bucket) is r._host


def test_first_run_excluded_from_cost_model():
    r = make_router(put_s=0.07, get_s=0.07)
    bucket = ("t", 64, 256)
    # first host run includes a multi-second XLA compile; recording it would
    # make the 140ms link look cheap and misroute every later group
    r.record(bucket, r._host, 5.0, first_run=True)
    assert r._host_ema.get(bucket) is None
    r.record(bucket, r._host, 0.015)
    assert r.choose(bucket) is r._host


def test_disabled_when_default_backend_is_host():
    r = BackendRouter()
    r._measured = True
    r.enabled = False
    assert r.choose(("t", 64, 64)) is None


def test_stats_shape():
    r = make_router(put_s=0.07, get_s=0.05)
    bucket = ("t", 64, 64)
    r.record(bucket, r._host, 0.01)
    s = r.stats()
    assert s["enabled"] and s["host_groups"] == 1 and s["accel_groups"] == 0
    assert s["link_put_ms"] == 70.0 and s["link_get_ms"] == 50.0


def test_link_cost_is_one_upload_and_one_fetch():
    """What a single-device group does since it crosses the link as one
    packed buffer each way (stream_processor_pipeline_device_uploads reads
    1.0 a group): the prediction counts the same transfers."""
    r = make_router(put_s=0.57e-3, get_s=0.44e-3)  # the v5e's measured link
    assert r.link_cost_s() == 0.57e-3 + 0.44e-3
    # under LOCAL_LINK_S: the accelerator takes a bucket's first group
    assert r.choose(("t", 64, 64)) is r._accel
