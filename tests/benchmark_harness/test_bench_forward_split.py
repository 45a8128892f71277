"""The forward split from inside: the tiny-scale traced body on the
CPU reports the five metrics that read the local's ``forward.encode``
/ ``forward.send`` stages and the global's ``import.*`` stages, and
their readers find nothing, without raising, in the rings of a
program that has no such stage."""

from bench_util import TINY, run_py

from benchmark import harness  # noqa: E402

NEW = ("forward_encode_ms", "forward_rpc_ms", "import_decode_ms",
       "import_lock_wait_ms", "import_apply_ms")


def test_traced_body_reports_the_forward_split():
    name = "local-wide-paced"
    c = harness.cell(name)
    res = harness.run_cell(c, seed=6, seconds=4.0, trace=True,
                           scale=TINY[name])
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in run_py().result_line(
        c, res, trace=True)["metrics"].items()}
    assert set(NEW) <= set(m)
    assert all(m[k] > 0 for k in NEW if k != "import_lock_wait_ms")
    assert m["import_lock_wait_ms"] >= 0
    # the two halves are inside the forward stage
    assert (m["forward_encode_ms"] + m["forward_rpc_ms"]
            <= m["forward_ms"] * 1.05)
    # every cycle of the window carries both sides' stages
    run = res["run"]
    for r in run["rings"]["local"]:
        if r["start_unix"] <= run["t_end"]:
            assert {"forward.encode", "forward.send"} <= set(r["stages"])
    assert any("import.apply" in r["stages"]
               for r in run["rings"]["global"])
    # the import is inside the call that carried it
    assert (m["import_decode_ms"] + m["import_lock_wait_ms"]
            + m["import_apply_ms"] <= m["forward_rpc_ms"])


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_import_readers_take_the_cycles_that_hold_the_windows_wires():
    """A global cycle holds the import of the local's previous tick:
    the cycle at the window's first instant (the last warm-up import)
    is left out, the one after the window's last local cycle is in."""
    ms = 1_000_000
    def imp(a):
        return {"import.decode": a * ms, "import.lock_wait": 0,
                "import.apply": 10 * a * ms,
                "import.device_step": a * ms}

    run = {"t0": 60.0, "t_end": 100.0, "interval_s": 10.0, "rings": {
        "local": [{"start_unix": 60.0 + 10 * i,
                   "stages": {"forward": 50 * ms,
                              "forward.encode": 30 * ms,
                              "forward.send": 18 * ms}}
                  for i in range(6)],      # the last is past t_end
        "global": [{"start_unix": 60.01, "stages": imp(100)}]
        + [{"start_unix": 70.01 + 10 * i, "stages": imp(1)}
           for i in range(5)]
        + [{"start_unix": 120.01, "stages": imp(100)}]}}
    assert _reader("import_decode_ms")(run) == 1.0
    assert _reader("import_lock_wait_ms")(run) == 0.0
    assert _reader("import_apply_ms")(run) == 11.0
    assert _reader("forward_encode_ms")(run) == 30.0
    assert _reader("forward_rpc_ms")(run) == 18.0


def test_readers_find_nothing_in_a_program_without_the_stages():
    """The parent commit's rings: the stages that were there then."""
    old = {"snapshot": 1, "dispatch": 1, "device_wait": 1,
           "host_emit": 1, "sink_flush": 1, "forward": 5_000_000}
    run = {"t0": 60.0, "t_end": 100.0, "interval_s": 10.0, "rings": {
        "local": [{"start_unix": 90.0, "stages": dict(old)}],
        "global": [{"start_unix": 100.0, "stages": dict(old)}]}}
    for name in NEW:
        assert _reader(name)(run) is None
    assert _reader("forward_ms")(run) == 5.0
