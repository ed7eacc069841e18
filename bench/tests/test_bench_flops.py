"""Operation counts from true shapes, against a count by hand."""
import pytest

from bench import flops, harness


def test_burgers_xpinn_2x2_by_hand():
    cfg = harness.load_json("configs", "burgers_xpinn_2x2.json")
    # layers 2->20, four 20->20, 20->1: 40 + 4 * 400 + 20 multiply-adds
    assert flops.layer_macs(cfg["nets"]["u"]) == 1660
    # every subdomain has 80 boundary points with data; four interfaces of
    # 20 points, each evaluated on both sides
    groups = flops.point_groups(cfg, [80] * 4, 4)
    assert groups == {"res": 80_000, "data": 320, "iface": 160}
    c = flops.step(cfg, groups)
    # residual and interface points carry u, u_x, u_t, u_xx (4 streams),
    # data points only u: 2 * 1660 * (4 * 80,000 + 320 + 4 * 160)
    fwd = 2 * 1660 * (4 * 80_000 + 320 + 4 * 160)
    assert fwd == 1_065_587_200
    assert c["kernel_res"]["flops"] == fwd
    assert c["kernel_bwd"]["flops"] == 2 * fwd
    assert c["step_flops"] == 3 * fwd
    # true-width bytes: x (2 floats), outputs (4 per point, 1 per data
    # point), and the 1660 + 100 + 1 + 5 parameters
    pts = 80_000 + 320 + 160
    outs = 4 * 80_000 + 320 + 4 * 160
    assert c["kernel_res"]["bytes"] == 4 * (2 * pts + outs + 1766)


def test_cpinn_interface_needs_no_second_derivative():
    cfg = harness.load_json("configs", "burgers_cpinn_4x1.json")
    g = flops.point_groups(cfg, [80] * 4, 3)
    f = flops.forward(cfg, g)["u"]["flops"]
    assert f == 2 * 1660 * (4 * 80_000 + 320 + 3 * 120)


def test_padding_is_not_work():
    """Nothing in the count reads the kernel's padded lane width."""
    import inspect

    src = inspect.getsource(flops)
    assert "WPAD" not in src and "128" not in src


@pytest.mark.parametrize("kind", ["burgers1d", "heat2d_inverse"])
def test_streams_cover_every_net(kind):
    name = {"burgers1d": "burgers_xpinn_2x2",
            "heat2d_inverse": "usmap_heat_10"}[kind]
    cfg = harness.load_json("configs", name + ".json")
    assert set(flops.STREAMS[kind]) == set(cfg["nets"])


def test_least_time_names_its_bound():
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    t, b = flops.least_seconds(197e12, 1.0, peak)
    assert b == "compute" and t == pytest.approx(1.0)
    t, b = flops.least_seconds(1.0, 819e9, peak)
    assert b == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peak("TPU v9 imaginary")
