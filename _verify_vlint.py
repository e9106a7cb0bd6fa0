"""Round-15 verify drive — vlint + sanitizer wiring, end to end.

Drives the static-analysis layer through its OPERATOR surfaces (the
`python -m tools.vlint` CLI, the baseline file, the `--json` snapshot
row, `make sanitize` + the TSan driver), and proves detection on the
REAL tree, not just the committed fixtures: a scratch copy of the
repo gets four live regressions seeded — an ABI field swap whose
total size still matches, a dropped generation bump, an unregistered
metric increment site, a time.sleep smuggled into a loop-registered
callback — and each must surface as exactly the expected finding
through the CLI with a nonzero exit.

Run: env PYTHONPATH=/root/repo JAX_PLATFORMS=cpu python _verify_vlint.py
"""
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

PASS = 0


def check(name, cond, detail=""):
    global PASS
    mark = "ok" if cond else "FAIL"
    print(f"[{mark}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        sys.exit(f"verify failed at: {name}")
    PASS += 1


def run_vlint(root, *args):
    r = subprocess.run(
        [sys.executable, "-m", "tools.vlint", "--root", root, *args],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": ROOT})
    return r.returncode, r.stdout


def scratch_tree(td):
    """A runnable copy of everything vlint reads."""
    for d in ("vproxy_tpu", "docs", "tests", "tools"):
        shutil.copytree(os.path.join(ROOT, d), os.path.join(td, d),
                        ignore=shutil.ignore_patterns(
                            "__pycache__", "*.so", "*.pyc"))
    return td


def edit(root, rel, old, new):
    p = os.path.join(root, rel)
    s = open(p).read()
    assert old in s, f"{rel}: seed anchor not found"
    open(p, "w").write(s.replace(old, new, 1))


def main():
    t0 = time.monotonic()

    # -- 1. the committed tree is clean, inside the tier-1 budget -----
    rc, out = run_vlint(ROOT)
    check("tree gate exit 0", rc == 0, out.strip().splitlines()[-1])
    check("tree gate: 0 open / 0 stale",
          "(0 open" in out and "0 stale baseline" in out)
    rc, out = run_vlint(ROOT, "--json")
    snap = json.loads(out)
    check("snapshot row shape",
          snap["open"] == 0 and snap["elapsed_s"] < 10.0
          and set(snap["findings_by_pass"]) <= {"abi", "gengate",
                                                "registry", "loop"},
          json.dumps(snap))

    # -- 2. live regressions on a scratch copy of the REAL tree ------
    with tempfile.TemporaryDirectory() as td:
        root = scratch_tree(td)

        # 2a. ABI: swap out_ip (u32) with a 4-byte array in the python
        # mirror — total size UNCHANGED, the old sizeof guards blind
        edit(root, "vproxy_tpu/net/vtl.py",
             'FLOW_REC = struct.Struct("<IH3s6s2s4s4sBBBB3s6s6sIHi")',
             'FLOW_REC = struct.Struct("<IH3s6s2s4s4sBBBB3s6s6s4sHi")')
        rc, out = run_vlint(root)
        check("ABI pass flags compensating field swap",
              rc == 1 and "abi:FLOW_REC:out_ip" in out,
              next((l for l in out.splitlines() if "out_ip" in l), ""))
        edit(root, "vproxy_tpu/net/vtl.py",
             '"<IH3s6s2s4s4sBBBB3s6s6s4sHi"',
             '"<IH3s6s2s4s4sBBBB3s6s6sIHi"')

        # 2b. gengate: MacTable.remove_iface loses its bump
        edit(root, "vproxy_tpu/vswitch/network.py",
             "    def remove_iface(self, iface) -> None:\n"
             "        for mac, (i, _) in list(self._e.items()):\n"
             "            if i is iface:\n"
             "                del self._e[mac]\n"
             "                self._bump()",
             "    def remove_iface(self, iface) -> None:\n"
             "        for mac, (i, _) in list(self._e.items()):\n"
             "            if i is iface:\n"
             "                del self._e[mac]")
        rc, out = run_vlint(root)
        check("gengate pass flags the dropped bump",
              rc == 1 and "gengate:MacTable.remove_iface:_e" in out)
        edit(root, "vproxy_tpu/vswitch/network.py",
             "                del self._e[mac]\n\n    def expire",
             "                del self._e[mac]\n                "
             "self._bump()\n\n    def expire")

        # 2c. registry: a typo'd metric family at an increment site
        edit(root, "vproxy_tpu/components/tcplb.py",
             '"vproxy_lb_retries_total"', '"vproxy_lb_retrys_total"')
        rc, out = run_vlint(root)
        check("registry pass flags the typo'd family",
              rc == 1
              and "metric-unregistered:vproxy_lb_retrys_total" in out)
        edit(root, "vproxy_tpu/components/tcplb.py",
             '"vproxy_lb_retrys_total"', '"vproxy_lb_retries_total"')

        # 2d. loop affinity: a sleep smuggled into a registered timer
        edit(root, "vproxy_tpu/net/eventloop.py",
             "    def _fire(self) -> None:\n"
             "        if self._stopped:\n"
             "            return\n",
             "    def _fire(self) -> None:\n"
             "        time.sleep(0.1)\n"
             "        if self._stopped:\n"
             "            return\n")
        rc, out = run_vlint(root)
        check("loop pass flags the sleeping timer callback",
              rc == 1 and "time.sleep" in out and "_fire" in out,
              next((l for l in out.splitlines() if "_fire" in l), ""))
        edit(root, "vproxy_tpu/net/eventloop.py",
             "        time.sleep(0.1)\n        if self._stopped:",
             "        if self._stopped:")

        # 2e. all seeds reverted -> the scratch tree is clean again
        rc, out = run_vlint(root)
        check("scratch tree clean after reverts", rc == 0)

        # 2f. baseline delta semantics: a brand-new unregistered
        # increment site fails the gate, baselining it passes, and
        # the entry going stale (site removed, entry kept) fails again
        probe_fn = ('\n\ndef _verify_probe(gi):\n'
                    '    gi.get_counter("vproxy_verify_probe_total")'
                    '.incr()\n')
        with open(os.path.join(root, "vproxy_tpu", "components",
                               "tcplb.py"), "a") as f:
            f.write(probe_fn)
        rc, out = run_vlint(root)
        check("new unregistered family fails the gate",
              rc == 1
              and "metric-unregistered:vproxy_verify_probe_total" in out)
        bl = os.path.join(root, "tools", "vlint", "baseline.toml")
        with open(bl, "a") as f:
            f.write('\n[[finding]]\npass = "registry"\n'
                    'key = "metric-unregistered:vproxy_verify_probe_'
                    'total"\nreason = "verify drive: deliberate"\n')
        rc, out = run_vlint(root)
        check("baselined finding passes the gate", rc == 0,
              out.strip().splitlines()[-1])
        edit(root, "vproxy_tpu/components/tcplb.py", probe_fn, "")
        rc, out = run_vlint(root)
        check("stale baseline entry fails the gate",
              rc == 1 and "stale" in out)

    # -- 3. sanitizer wiring (gated on toolchain, like the test) -----
    probe = subprocess.run(
        ["g++", "-fsanitize=thread", "-fPIC", "-shared", "-o",
         "/dev/null", "-x", "c++", "-"],
        input="int main(){return 0;}", capture_output=True, text=True)
    if probe.returncode != 0 or shutil.which("make") is None:
        print("[skip] sanitizer drive: toolchain lacks -fsanitize=thread")
    else:
        native = os.path.join(ROOT, "vproxy_tpu", "native")
        r = subprocess.run(["make", "sanitize"], cwd=native,
                           capture_output=True, text=True, timeout=600)
        check("make sanitize builds both variants", r.returncode == 0
              and os.path.exists(os.path.join(native, "libvtl-tsan.so"))
              and os.path.exists(os.path.join(native, "libvtl-asan.so")))
        rt = subprocess.run(["gcc", "-print-file-name=libtsan.so.0"],
                            capture_output=True, text=True
                            ).stdout.strip()
        with tempfile.TemporaryDirectory() as td:
            logp = os.path.join(td, "tsan")
            env = {k: v for k, v in os.environ.items()
                   if k != "LD_PRELOAD"}
            env.update({
                "LD_PRELOAD": rt,
                "VPROXY_TPU_VTL_SO": os.path.join(native,
                                                  "libvtl-tsan.so"),
                "VPROXY_TPU_FD_PROVIDER": "native",
                "SAN_DRIVER_S": "5",
                "TSAN_OPTIONS": f"exitcode=66 log_path={logp}"})
            r = subprocess.run(
                [sys.executable,
                 os.path.join(ROOT, "tests", "_sanitize_driver.py")],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=300)
            logs = ""
            for fn in os.listdir(td):
                if fn.startswith("tsan"):
                    logs += open(os.path.join(td, fn)).read()
            m = re.search(r"DRIVER_OK (\{.*\})", r.stdout)
            check("TSan drive: zero data races + hot paths exercised",
                  r.returncode == 0 and m is not None
                  and "WARNING: ThreadSanitizer" not in logs,
                  m.group(1) if m else r.stdout[-200:])

    print(f"\nALL {PASS} CHECKS PASSED in "
          f"{time.monotonic() - t0:.1f}s")


if __name__ == "__main__":
    main()
