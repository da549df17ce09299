"""HLO cost model: trip counts, sharded flops, collective bytes, DS/DUS."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(n, code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_scan_trip_count_flops():
    out = run_with_devices(1, """
        import jax, jax.numpy as jnp
        from repro.launch import hlo_analysis as H
        def g(x):
            def body(c, _):
                return c @ c.T @ c * 0.99, None
            return jax.lax.scan(body, x, None, length=7)[0]
        hlo = jax.jit(g).lower(jax.ShapeDtypeStruct((64,64), jnp.float32)).compile().as_text()
        mc = H.analyze(hlo)
        expect = 7 * 2 * 2 * 64**3
        assert abs(mc.flops - expect) / expect < 0.01, (mc.flops, expect)
        print("OK", mc.flops)
    """)
    assert "OK" in out


def test_sharded_matmul_per_device_flops_and_allreduce():
    out = run_with_devices(16, """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch import hlo_analysis as H
        mesh = jax.make_mesh((4, 4), ("data", "model"))
        x = jax.ShapeDtypeStruct((64,128), jnp.float32, sharding=NamedSharding(mesh, P("data", None)))
        w = jax.ShapeDtypeStruct((128,256), jnp.float32, sharding=NamedSharding(mesh, P(None, "model")))
        hlo = jax.jit(lambda x, w: x @ w).lower(x, w).compile().as_text()
        mc = H.analyze(hlo)
        assert mc.flops == 2*64*128*256/16, mc.flops
        # contracting psum case
        w2 = jax.ShapeDtypeStruct((128,256), jnp.float32, sharding=NamedSharding(mesh, P("model", None)))
        x2 = jax.ShapeDtypeStruct((64,128), jnp.float32, sharding=NamedSharding(mesh, P("data", "model")))
        def f(x, w):
            # explicit mesh axes: a dot over sharded contracting dims names
            # its output sharding (the psum the compiler must insert)
            return jnp.dot(x, w, out_sharding=NamedSharding(mesh, P("data", None)))
        hlo2 = jax.jit(f).lower(x2, w2).compile().as_text()
        mc2 = H.analyze(hlo2)
        assert mc2.coll_bytes > 0, mc2.coll_by_kind
        print("OK")
    """)
    assert "OK" in out


def test_convert_artifacts_excluded():
    out = run_with_devices(1, """
        import jax, jax.numpy as jnp
        from repro.launch import hlo_analysis as H
        # a bf16 program on CPU inserts f32 emulation converts
        def f(x):
            return (x @ x).astype(jnp.bfloat16) @ x
        hlo = jax.jit(f).lower(jax.ShapeDtypeStruct((128,128), jnp.bfloat16)).compile().as_text()
        mc = H.analyze(hlo)
        # flops counted, bytes finite & not absurdly larger than tensors
        assert mc.flops >= 2 * 2 * 128**3 * 0.99
        assert mc.hbm_bytes < 60 * 128 * 128 * 4, mc.hbm_bytes
        print("OK")
    """)
    assert "OK" in out


def test_parse_module_handles_entry_and_params():
    from repro.launch import hlo_analysis as H

    hlo = """\
HloModule m

%helper (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %e = f32[4]{0} exponential(%p)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %f = f32[4]{0} fusion(%a), kind=kLoop, calls=%helper
}
"""
    comps = H.parse_module(hlo)
    assert set(comps) == {"helper", "main"}
    assert comps["helper"].params == {"p": "f32[4]"}
