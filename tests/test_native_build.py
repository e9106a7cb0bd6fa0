"""Native-build guard: a committed libvtl.so must never drift from
vtl.cpp.

Rebuilds via native/Makefile when the source is newer than the .so
(make's own staleness rule), then asserts the freshly-built library
exports the current ABI surface — including the flow-cache symbols —
and that the C install-record size matches the Python struct packing
bit for bit. Catches the "stale committed .so" failure mode where the
pure-Python fallback (or an AttributeError at ctypes bind time) would
otherwise silently disable whole subsystems.
"""
import ctypes
import os
import shutil
import subprocess
import sys

import pytest

NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "vproxy_tpu",
                          "native")
SO = os.path.join(NATIVE_DIR, "libvtl.so")

REQUIRED_SYMBOLS = (
    # event loop + sockets + pump (the pre-existing surface)
    "vtl_new", "vtl_poll", "vtl_free", "vtl_pump_new", "vtl_pump_connect",
    "vtl_pump_counters", "vtl_recvmmsg", "vtl_sendmmsg",
    # switch flow cache (PR-5 surface)
    "vtl_flowcache_new", "vtl_flowcache_free", "vtl_switch_gen_bump",
    "vtl_switch_gen", "vtl_switch_poll", "vtl_flow_install",
    "vtl_flowcache_counters", "vtl_flowcache_stat", "vtl_flow_rec_size",
    "vtl_wait_readable",
    # accept lanes (this PR's surface) + the io_uring probe
    "vtl_lanes_new", "vtl_lanes_free", "vtl_lanes_close_listeners",
    "vtl_lanes_shutdown", "vtl_lanes_port", "vtl_lanes_engine",
    "vtl_lanes_set_punt_all", "vtl_lanes_set_limit",
    "vtl_lanes_set_shed",  # adaptive overload: C-side RST shed (r10)
    "vtl_close_rst",       # one-call RST close for the shed path (r10)
    "vtl_lanes_set_timeout", "vtl_lanes_stat", "vtl_lanes_active",
    "vtl_lanes_errno",
    "vtl_lane_counters", "vtl_lane_gen", "vtl_lane_gen_bump",
    "vtl_lane_install", "vtl_lane_poll", "vtl_lane_rec_size",
    "vtl_lane_punt_size", "vtl_uring_probe",
    # maglev consistent-hash pick (r11): lane route install, the parity
    # pick surface, and the flow-cache table attach
    "vtl_maglev_rec_size", "vtl_maglev_pick", "vtl_lane_maglev_install",
    "vtl_flow_maglev_install", "vtl_flow_maglev_pick",
    # span tracing + lane stage histograms (r13): SPSC span rings per
    # lane, the sampling knob, and the stat-ABI widening that folds
    # lane connections into vproxy_accept_stage_us
    "vtl_trace_rec_size", "vtl_trace_set_sample", "vtl_trace_set_ring_cap",
    "vtl_trace_drain", "vtl_trace_counters", "vtl_lanes_stage_stat",
    # traffic-analytics HH shards (r14): per-lane sketch shards, the
    # flow-cache hit drain, and the py==C hash parity surface
    "vtl_hh_rec_size", "vtl_hh_set_enabled", "vtl_hh_hash",
    "vtl_hh_counters", "vtl_hh_drain", "vtl_hh_flow_drain",
    # workload capture (r16): lane-plane inter-arrival + per-connection
    # bytes/duration histograms and the capture knob
    "vtl_lanes_capture_stat", "vtl_workload_set_enabled",
    # policing probe (r19): the POLICE_REC admission table, its knob,
    # the generation-stamped install, and the parity check surface
    "vtl_police_rec_size", "vtl_police_set_enabled", "vtl_police_install",
    "vtl_police_counters", "vtl_police_check",
)


def test_native_so_rebuilds_and_exports_current_abi():
    if shutil.which("make") is None or shutil.which("g++") is None:
        if not os.path.exists(SO):
            pytest.skip("no toolchain and no prebuilt libvtl.so")
    else:
        r = subprocess.run(["make", "-s"], cwd=NATIVE_DIR,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, f"native build failed: {r.stderr[:500]}"
        src = os.path.join(NATIVE_DIR, "vtl.cpp")
        assert os.path.getmtime(SO) >= os.path.getmtime(src), \
            "make left libvtl.so older than vtl.cpp"
    lib = ctypes.CDLL(SO)
    missing = [s for s in REQUIRED_SYMBOLS if not hasattr(lib, s)]
    assert not missing, f"libvtl.so lacks symbols: {missing}"
    from vproxy_tpu.net import vtl

    # Shared-record ABI: assertions GENERATED from vlint's extracted
    # struct model (tools/vlint/structs.py parses both sides of every
    # mirror) instead of a hand-maintained size list — the model is
    # the single source of truth, this proves the COMPILED .so agrees
    # with it, and the runtime vtl_*_rec_size guards in net/vtl.py
    # stay as the load-time backstop for prebuilt libraries.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.vlint import structs as vstructs
    model = vstructs.shared_model(os.path.join(NATIVE_DIR, "..", ".."))
    size_fns = {"FLOW_REC": lib.vtl_flow_rec_size,
                "LANE_REC": lib.vtl_lane_rec_size,
                "LANE_PUNT": lib.vtl_lane_punt_size,
                "MAGLEV_REC": lib.vtl_maglev_rec_size,
                "TRACE_REC": lib.vtl_trace_rec_size,
                "HH_REC": lib.vtl_hh_rec_size,
                "POLICE_REC": lib.vtl_police_rec_size}
    assert set(size_fns) == set(model), \
        "a shared record gained/lost its vtl_*_rec_size guard — " \
        "update size_fns AND vlint's SHARED_RECORDS together"
    for py_name, (py_rec, c_rec) in sorted(model.items()):
        runtime = getattr(vtl, py_name)
        assert runtime.size == py_rec.size, \
            f"{py_name}: loaded struct.Struct disagrees with the " \
            f"parsed model (vlint parser drift)"
        assert int(size_fns[py_name]()) == c_rec.size == py_rec.size, \
            f"{py_name}: compiled C sizeof({c_rec.name}) drifted " \
            f"from the mirror"
        assert len(py_rec.fields) == len(c_rec.fields), \
            f"{py_name}: field count drifted (zip would truncate)"
        for pf, cf in zip(py_rec.fields, c_rec.fields):
            assert (pf.name, pf.offset, pf.size, pf.kind) == \
                (cf.name, cf.offset, cf.size, cf.kind), \
                f"{py_name}.{pf.name} drifted from C " \
                f"{c_rec.name}.{cf.name}"

    assert len(vtl.flowcache_counters()) == 5 + len(vtl.FLOW_DROP_REASONS)
    assert len(vtl.lane_counters()) == 5
    # span-id / stage-id tables must cover every C TR_* / LANE_STAGE_*
    assert len(vtl.TRACE_SPANS) == 7
    assert len(vtl.POLICE_ACTIONS) == 3  # POLICE_ACT_* contract
    assert len(vtl.trace_counters()) == 2
    assert len(vtl.LANE_STAGES) == 3


def _toolchain() -> bool:
    return shutil.which("make") is not None and shutil.which("g++") is not None


def test_tier1_runs_on_the_native_provider():
    """Where a toolchain exists the silent fallback is a failure: a
    worker on the pure-python provider skips the native-gated files and
    the count stops being a property of the tree."""
    if not _toolchain():
        pytest.skip("no toolchain: the py fallback is the provider")
    if os.environ.get("VPROXY_TPU_FD_PROVIDER") == "py":
        pytest.skip("py provider requested")
    from vproxy_tpu.net import vtl
    assert vtl.PROVIDER == "native"


def test_concurrent_first_imports_build_once_and_all_load_native(tmp_path):
    """A fresh checkout has no libvtl.so and xdist starts every worker
    at once: all of them must end on the native provider (no loader
    maps a half-written file) and exactly one of them compiles."""
    if not _toolchain():
        pytest.skip("no toolchain")
    pkg = tmp_path / "vproxy_tpu"
    root = os.path.join(NATIVE_DIR, "..")
    for rel in ("__init__.py", "net/__init__.py", "net/vtl.py",
                "net/vtl_py.py", "native/Makefile", "native/vtl.cpp"):
        (pkg / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(root, rel), pkg / rel)
    # the Makefile takes CXX from the environment: count the compiles
    cxx = tmp_path / "cxx.sh"
    cxx.write_text('#!/bin/sh\necho x >> "$CXX_COUNT"\nexec g++ "$@"\n')
    cxx.chmod(0o755)
    env = {k: v for k, v in os.environ.items()
           if k not in ("VPROXY_TPU_FD_PROVIDER", "VPROXY_TPU_VTL_SO")}
    env.update(PYTHONPATH=str(tmp_path), CXX=str(cxx),
               CXX_COUNT=str(tmp_path / "compiles"))
    procs = [subprocess.Popen(
        [sys.executable, "-c",
         "from vproxy_tpu.net import vtl; print(vtl.PROVIDER, vtl.__file__)"],
        cwd=tmp_path, env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE) for _ in range(6)]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err[-800:]
        assert out.split() == ["native", str(pkg / "net" / "vtl.py")], \
            (out, err[-800:])
        assert "unavailable" not in err, err[-800:]
    assert (tmp_path / "compiles").read_text().count("x") == 1
    assert sorted(os.listdir(pkg / "native")) == [
        ".build.lock", "Makefile", "libvtl.so", "vtl.cpp"]


def test_uring_probe_contract():
    """The io_uring probe is a stable bitmask (bit0 setup, bits 1-5
    opcodes), cached, and never a precondition: lanes must come up on
    the epoll engine when the kernel denies io_uring (this container's
    4.4 kernel returns 0)."""
    from vproxy_tpu.net import vtl
    if not vtl.lanes_supported():
        pytest.skip("no lane symbols in the loaded provider")
    m = vtl.uring_probe()
    assert 0 <= m < 64
    assert m == vtl.uring_probe()  # cached, stable
    f = vtl.uring_probe_fields()
    assert set(f) == {"setup", "accept", "connect", "poll", "splice",
                      "send_zc"}
    if not f["setup"]:  # opcode bits require a working setup
        assert m == 0


def test_both_engine_paths_compile():
    """A kernel (or header set) without io_uring must still build and
    test the epoll lanes: the engine ABI is self-defined in vtl.cpp and
    -DVTL_NO_URING compiles the ring engine out entirely. Both
    configurations must at least pass the compiler."""
    if shutil.which("g++") is None:
        pytest.skip("no toolchain")
    src = os.path.join(NATIVE_DIR, "vtl.cpp")
    for flags in ([], ["-DVTL_NO_URING"]):
        r = subprocess.run(
            ["g++", "-O0", "-std=c++17", "-fPIC", "-Wall", "-Wextra",
             "-fsyntax-only", *flags, src],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, \
            f"engine path {flags or ['default']} failed to compile: " \
            f"{r.stderr[:800]}"
