"""chip_smoke.py and the bring-up plumbing around it, on the CPU.

The smoke itself only passes on a TPU; here its legs run at tiny sizes
(the platform check lives in main(), which the tests never call), and
the properties that keep a dead device from hiding are pinned down:
the script refuses the CPU, the compile cache has one fixed place,
importing the load-generator tools leaves the platform alone.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(code_or_args, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    args = code_or_args if isinstance(code_or_args, list) \
        else [sys.executable, "-c", code_or_args]
    return subprocess.run(args, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)


def test_legs_at_tiny_size():
    import chip_smoke as S
    del S.FAILURES[:]
    served = S.served_leg(n_groups=8, bursts=2, burst=8)
    assert served["device"]["device_queries"] == 16
    assert served["device"]["oracle_queries"] == 0
    width = S.width_leg(S.JaxLog(), n_rules=1000, n_routes=500,
                        n_acls=200, n_queries=256, threads=4, sample=16)
    assert width["service"]["failovers"] == 0
    assert width["fused"] == {"available": True, "kernel": "jit",
                              "packed_bytes": width["fused"]["packed_bytes"]}
    grouped = S.grouped_leg(n_groups=4, per_group=3, burst=8)
    assert grouped["before"]["device_picks"] == 8 == \
        grouped["after"]["device_picks"]
    assert grouped["row_builds"] == 1
    assert S.FAILURES == []


def test_a_failover_fails_the_smoke():
    """Correct answers are not enough: a dispatch that failed over to
    the host oracle still answers right, and must still fail the leg."""
    import chip_smoke as S
    from vproxy_tpu.utils import failpoint
    del S.FAILURES[:]
    failpoint.arm("device.dispatch.error", count=1)
    try:
        S.served_leg(n_groups=4, bursts=1, burst=4)
    finally:
        failpoint.clear()
    assert any("device did not answer every query" in f
               and "failpoint device.dispatch.error" in f
               for f in S.FAILURES), S.FAILURES
    del S.FAILURES[:]


def test_script_refuses_the_cpu():
    p = _run([sys.executable, "chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "platform is 'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_compile_cache_has_one_place(tmp_path):
    code = ("import os, jax\n"
            "from vproxy_tpu.utils.jaxenv import compile_cache_dir\n"
            "print(compile_cache_dir()); "
            "print(jax.config.jax_compilation_cache_dir)")
    want = str(tmp_path / "cc")
    p = _run(code, {"JAX_COMPILATION_CACHE_DIR": want})
    assert p.stdout.split() == [want, want], p.stderr  # jax's own handling
    fixed = os.path.join(REPO, ".jax_cache")
    p = _run(code, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert p.stdout.split() == [fixed, fixed], p.stderr


def test_importing_the_tools_leaves_the_platform_alone():
    code = ("import os, sys\n"
            "sys.path.insert(0, 'tools')\n"
            "import replay, storm, chaos\n"
            "assert 'JAX_PLATFORMS' not in os.environ, os.environ\n"
            "assert 'XLA_FLAGS' not in os.environ, os.environ\n")
    p = _run(code, drop=("JAX_PLATFORMS", "XLA_FLAGS"))
    assert p.returncode == 0, p.stderr
