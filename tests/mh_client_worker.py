"""Subprocess worker for the multi-host CLIENT test (not collected by
pytest).  Joins the two-process jax.distributed cluster and runs a full
``TpuCrackClient`` volunteer loop: process 0 fetches/submits over the
real socket server started by the parent test, process 1 receives the
unit only through the client's broadcast layer — the "multi-host slice
as ONE very large volunteer" contract (client/main.py run())."""

import os
import sys


def main():
    pid = int(sys.argv[1])
    coord_port = sys.argv[2]
    http_port = sys.argv[3]
    workdir = sys.argv[4]
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dwpa_tpu.utils.compcache import enable_compilation_cache

    enable_compilation_cache(os.path.join(REPO, ".pytest_xla_cache"))

    from dwpa_tpu.parallel.mesh import multihost_mesh

    multihost_mesh(coordinator=f"localhost:{coord_port}",
                   num_processes=2, process_id=pid)
    assert jax.process_count() == 2

    import dwpa_tpu
    import dwpa_tpu.client.main as cm
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient

    if len(sys.argv) > 5 and sys.argv[5]:
        # simulate a host running a different client build (the
        # mixed-version negative test): the slice must refuse to start
        dwpa_tpu.__version__ = cm.__version__ = sys.argv[5]

    cfg = ClientConfig(
        base_url=f"http://127.0.0.1:{http_port}/",
        workdir=os.path.join(workdir, f"host{pid}"),
        max_work_units=1, batch_size=128,
    )
    client = TpuCrackClient(
        cfg, log=lambda *a: print(f"[{pid}]", *a, flush=True))
    n = client.run()
    pot = ""
    if os.path.exists(client.potfile):
        pot = open(client.potfile).read().strip()
    print(f"MHCLIENT {pid} done={n} pot={'yes' if pot else 'no'}", flush=True)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
