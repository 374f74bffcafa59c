"""Multi-host hierarchical-collective smoke bench (ISSUE 9).

Runs the SAME hierarchical two-level train step at two process counts —

  1 process x 4 virtual devices   (host axis emulated: 2x2 fold)
  2 processes x 4 virtual devices (host axis real: the inter-host shard
                                   exchange is a cross-process ppermute
                                   over gloo)

— each with an in-leg parity probe (hier vs psum on the same mesh,
3 optimizer steps from identical init) and a timed throughput section.
Per-device batch is FIXED (weak scaling): the 2-process leg does twice
the global work over twice the devices, so img/s-per-device directly
reads out what adding a host costs.

    python benches/comm_multihost.py          # parent: both legs + gate
    python benches/comm_multihost.py leg      # one measurement process

Parent prints parseable lines and exits 0 iff BOTH legs hold the ≤1e-5
hier-vs-psum parity contract:

    MULTIHOST_ROW procs=.. devices=.. ips=.. ips_per_dev=.. parity=..
    MULTIHOST_WEAK_SCALING eff=..   (per-dev 2proc / per-dev 1proc)
    COMM_MULTIHOST_GATE PASS|FAIL ...

On the CPU harness the "DCN" is localhost gloo — the efficiency number
is indicative; the parity gate is the hard contract either way.

One process per chip: this is a CPU-only tool. The parent never imports
JAX (it only spawns and parses), and every child pins
``jax_platforms="cpu"`` before its first device query — so nothing here
can take, or wait for, an accelerator that another process holds. Keep
it that way: a leg that needs the chip must run alone, in one process.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PER_DEV_BATCH = 16
PROBE_STEPS = 3
TIMED_STEPS = 8
IN_SHAPE = (8, 8, 3)
PARITY_TOL = 1e-5


def run_leg() -> int:
    """One measurement process: joins the multi-process runtime when the
    PCNN_* env is set (2-proc leg), else runs single-process with an
    emulated 2-host fold of its 4 virtual devices — identical algorithm,
    only the host-axis transport differs."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    # Cross-process collectives on the CPU backend go through gloo; the
    # default ("none") hard-errors on the first multiprocess computation.
    # Single-process legs must NOT set it — without a distributed client
    # the gloo factory refuses to build the CPU backend at all.
    if os.environ.get("PCNN_COORDINATOR"):
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:  # newer jax: gloo is the default, knob gone
            pass

    import numpy as np

    from parallel_cnn_tpu.parallel import distributed

    joined = distributed.initialize()

    import jax.numpy as jnp  # noqa: F401  (post-init import discipline)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from parallel_cnn_tpu.config import CommConfig
    from parallel_cnn_tpu.nn import core, layers
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.train import zoo

    mesh = (mesh_lib.make_hier_mesh() if joined
            else mesh_lib.make_hier_mesh(n_hosts=2))
    n_total = mesh.devices.size
    global_batch = PER_DEV_BATCH * n_total

    model = core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.BatchNorm(), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])
    opt = zoo.make_optimizer(lr=0.05)

    rng = np.random.default_rng(456)
    x_host = rng.normal(size=(global_batch,) + IN_SHAPE).astype(np.float32)
    y_host = rng.integers(0, 10, (global_batch,)).astype(np.int32)

    def globalize(a, sharding):
        # make_array_from_callback: each process materializes only its
        # addressable shards — works identically at 1 and 2 processes.
        host = np.asarray(a)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx]
        )

    rep = NamedSharding(mesh, P())
    dat = mesh_lib.batch_sharding(mesh)
    x = globalize(x_host, dat)
    y = globalize(y_host, dat)

    def init_state():
        st = zoo.init_state(model, jax.random.key(7), IN_SHAPE, opt)
        return jax.tree_util.tree_map(lambda a: globalize(a, rep), st)

    losses = {}
    steps = {}
    for name, comm in (
        ("psum", CommConfig(impl="psum")),
        ("hier", CommConfig(impl="hierarchical", bucket_bytes=2048)),
    ):
        step = zoo.make_train_step(
            model, opt, accum_steps=2, mesh=mesh, comm=comm
        )
        st, loss = init_state(), None
        for _ in range(PROBE_STEPS):
            st, loss = step(st, x, y)
        jax.block_until_ready(loss)
        losses[name] = float(loss)
        steps[name] = step
    parity = abs(losses["hier"] - losses["psum"])

    # Timed section: the hier step is already compiled (probe above);
    # chain states so the donated buffers stay live.
    st = init_state()
    st, loss = steps["hier"](st, x, y)  # warm donation path
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        st, loss = steps["hier"](st, x, y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    ips = TIMED_STEPS * global_batch / dt

    if jax.process_index() == 0:
        print(
            f"LEG procs={jax.process_count()} devices={n_total} "
            f"ips={ips:.2f} ips_per_dev={ips / n_total:.2f} "
            f"parity={parity:.3e}",
            flush=True,
        )
    return 0


def _leg_env(extra=None):
    env = dict(os.environ)
    for var in ("PCNN_COORDINATOR", "PCNN_NUM_PROCESSES", "PCNN_PROCESS_ID"):
        env.pop(var, None)
    # 4 virtual devices per process; run_leg pins the platform itself.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    if extra:
        env.update(extra)
    return env


def _parse_leg(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("LEG "):
            return {
                k: v for k, v in
                (tok.split("=", 1) for tok in line.split()[1:])
            }
    raise RuntimeError(f"no LEG line in output:\n{stdout}")


def main() -> int:
    me = os.path.abspath(__file__)

    # Leg 1: single process, emulated 2-host mesh. A fresh interpreter so
    # the platform/device-count env is snapshotted cleanly.
    r1 = subprocess.run(
        [sys.executable, me, "leg"], env=_leg_env(), capture_output=True,
        text=True, timeout=600,
    )
    if r1.returncode != 0:
        print(r1.stdout, r1.stderr, sep="\n")
        print("COMM_MULTIHOST_GATE FAIL 1-proc leg crashed "
              f"(rc {r1.returncode})")
        return 1
    leg1 = _parse_leg(r1.stdout)

    # Leg 2: two real processes over a localhost coordinator.
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, me, "leg"],
            env=_leg_env({
                "PCNN_COORDINATOR": f"127.0.0.1:{port}",
                "PCNN_NUM_PROCESSES": "2",
                "PCNN_PROCESS_ID": str(rank),
            }),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(rc != 0 for rc, _, _ in outs):
        for rc, out, err in outs:
            print(f"--- rank rc={rc} ---\n{out}\n{err}")
        print("COMM_MULTIHOST_GATE FAIL 2-proc leg crashed")
        return 1
    leg2 = _parse_leg(outs[0][1])

    p1, p2 = float(leg1["parity"]), float(leg2["parity"])
    d1, d2 = float(leg1["ips_per_dev"]), float(leg2["ips_per_dev"])
    for leg in (leg1, leg2):
        print(
            f"MULTIHOST_ROW procs={leg['procs']} devices={leg['devices']} "
            f"ips={leg['ips']} ips_per_dev={leg['ips_per_dev']} "
            f"parity={leg['parity']}"
        )
    eff = d2 / d1 if d1 > 0 else 0.0
    print(f"MULTIHOST_WEAK_SCALING eff={eff:.3f}")
    ok = p1 <= PARITY_TOL and p2 <= PARITY_TOL
    print(
        f"COMM_MULTIHOST_GATE {'PASS' if ok else 'FAIL'} "
        f"parity_1proc={p1:.3e} parity_2proc={p2:.3e} tol={PARITY_TOL:.0e}"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "leg":
        sys.exit(run_leg())
    sys.exit(main())
