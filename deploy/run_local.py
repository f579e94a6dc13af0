"""Bring up the full cluster as OS processes and run the e2e loop —
the docker-compose topology without containers (CI / dev machines
without a docker daemon; the container path is deploy/docker-compose.yaml
with the SAME services and the SAME deploy/e2e_loop.py).

  python deploy/run_local.py          # exit 0 = cluster up + loop passed
  python deploy/run_local.py --mtls   # same, with auto-issued mTLS on the
                                      # piece plane (manager-hosted CA)
  python deploy/run_local.py --replicas N
                                      # N scheduler replicas: daemons
                                      # steer tasks by consistent hash,
                                      # probe graph shared via the manager
"""

from __future__ import annotations

import os
import re
import select
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIECE = 64 * 1024


def child_env(name: str) -> dict:
    """Environment of one cluster child.  An accelerator belongs to one
    process at a time, and the trainer is the process that needs it: it
    inherits the caller's JAX platform, while every other child
    (manager, schedulers, daemons, the e2e driver) is pinned to the CPU
    so that importing JAX can never take the chip away from it."""
    env = {**os.environ, "PYTHONPATH": REPO}
    if name != "trainer":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def main() -> int:
    mtls = "--mtls" in sys.argv[1:]
    # --manager-standby: launch a leader+hot-standby manager pair
    # (manager/replication.py); clients get BOTH urls and fail over.
    manager_standby = "--manager-standby" in sys.argv[1:]
    replicas = 1
    argv = sys.argv[1:]
    if "--replicas" in argv:
        i = argv.index("--replicas")
        # Value optional: bare "--replicas" means 2.
        if i + 1 < len(argv) and argv[i + 1].isdigit():
            replicas = max(int(argv[i + 1]), 1)
        else:
            replicas = 2
    tmp = tempfile.mkdtemp(prefix="df-local-")
    procs = []

    def write(name: str, text: str) -> str:
        path = os.path.join(tmp, name)
        with open(path, "w") as f:
            f.write(text)
        return path

    def spawn(name, argv, ready_prefixes, extra_env=None):
        proc = subprocess.Popen(
            [sys.executable, "-m", *argv],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**child_env(name), **(extra_env or {})},
        )
        procs.append(proc)
        # A reader THREAD owns the pipe: mixing select() on the fd with
        # buffered readline() can strand a ready line in the Python-side
        # buffer (stderr is merged, so log lines coalesce with it in one
        # OS read) and falsely declare the service dead.
        import queue

        # Bounded: after readiness nobody consumes — the pump drops the
        # oldest instead of retaining every log line for the cluster's
        # lifetime, and keeps reading so the child never blocks on a
        # full pipe.
        lines: "queue.Queue" = queue.Queue(maxsize=1000)

        def pump() -> None:
            for raw in proc.stdout:
                while True:
                    try:
                        lines.put_nowait(raw)
                        break
                    except queue.Full:
                        try:
                            lines.get_nowait()
                        except queue.Empty:
                            pass

        threading.Thread(target=pump, name=f"pump-{name}", daemon=True).start()
        found = {}
        deadline = time.time() + 60
        while time.time() < deadline and len(found) < len(ready_prefixes):
            try:
                line = lines.get(timeout=max(deadline - time.time(), 0.1)).strip()
            except queue.Empty:
                break
            for p in ready_prefixes:
                if line.startswith(p):
                    found[p] = line
        if len(found) != len(ready_prefixes):
            raise SystemExit(f"run_local: {name} never became ready ({found})")
        print(f"run_local: {name} up", flush=True)
        return found

    try:
        # The HA pair shares a generated lease secret (config validation
        # refuses the public default — it would let anyone forge leases
        # or fetch the replicated state).
        import secrets as _secrets

        ha_yaml = (
            "ha: {enable: true, lease_ttl_s: 5.0, "
            f"lease_secret: {_secrets.token_hex(16)}}}\n"
        )
        mcfg = write("manager.yaml", (
            "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
            f"registry: {{blob_dir: {tmp}/manager}}\n"
            + (ha_yaml if manager_standby else "")
            + (f"ca_dir: {tmp}/ca\n" if mtls else "")
        ))
        mout = spawn("manager", ["dragonfly2_tpu.cli.manager", "--config", mcfg],
                     ["manager: serving"])
        manager_url = re.search(r"REST on (\S+)", mout["manager: serving"]).group(1)
        manager_urls = manager_url
        if manager_standby:
            sbmcfg = write("manager-standby.yaml", (
                "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
                f"registry: {{blob_dir: {tmp}/manager-standby}}\n"
                + ha_yaml
            ))
            sbout = spawn(
                "manager-standby",
                ["dragonfly2_tpu.cli.manager", "--config", sbmcfg,
                 "--replicate-from", manager_url],
                ["manager: serving"],
            )
            standby_url = re.search(
                r"REST on (\S+)", sbout["manager: serving"]
            ).group(1)
            # Every manager client takes the pair: comma-separated spec
            # feeds rpc/resolver.ManagerEndpoints.
            manager_urls = f"{manager_url},{standby_url}"

        tcfg = write("trainer.yaml", (
            "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
            f"data_dir: {tmp}/trainer\n"
            "training: {epochs: 6, learning_rate: 0.003, warmup_steps: 10}\n"
        ))
        tout = spawn("trainer",
                     ["dragonfly2_tpu.cli.trainer", "--config", tcfg,
                      "--manager", manager_url],
                     ["trainer: device", "trainer: ingest"])
        # The trainer is the one child not pinned to the CPU: show what
        # it came up on.
        print(f"run_local: {tout['trainer: device']}", flush=True)
        trainer_url = re.search(r"ingest on (\S+?)[, ]",
                                tout["trainer: ingest"] + " ").group(1)

        scfg = write("scheduler.yaml", (
            "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
            "scheduling: {retry_interval_s: 0.1}\n"
            f"storage: {{dir: {tmp}/records, buffer_size: 1}}\n"
            f"manager_addr: {manager_urls}\n"
            "dynconfig_refresh_s: 5.0\n"
            + ("topology_sync_interval_s: 3.0\n" if replicas > 1
               else "topology_sync_interval_s: 10.0\n")
            + ("security: {auto_issue: true}\n" if mtls else "")
        ))
        sout = spawn("scheduler",
                     ["dragonfly2_tpu.cli.scheduler", "--config", scfg],
                     ["scheduler: serving"])
        scheduler_url = re.search(r"rpc on (\S+?),",
                                  sout["scheduler: serving"] + ",").group(1)
        replica_urls = []
        for n in range(1, replicas):
            # Replica N: same manager, own storage — the probe graph
            # crosses replicas only through the manager's topology sync.
            sbcfg = write(f"scheduler-{n}.yaml", (
                "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
                "scheduling: {retry_interval_s: 0.1}\n"
                f"storage: {{dir: {tmp}/records-{n}, buffer_size: 1}}\n"
                f"manager_addr: {manager_urls}\n"
                "dynconfig_refresh_s: 5.0\n"
                "topology_sync_interval_s: 3.0\n"
                + ("security: {auto_issue: true}\n" if mtls else "")
            ))
            sbout = spawn(f"scheduler-{n}",
                          ["dragonfly2_tpu.cli.scheduler", "--config", sbcfg],
                          ["scheduler: serving"])
            replica_urls.append(re.search(
                r"rpc on (\S+?),", sbout["scheduler: serving"] + ","
            ).group(1))
        scheduler_b_url = replica_urls[0] if replica_urls else ""

        # Auto-issued mTLS: every daemon bootstraps its identity from the
        # manager's cluster CA at boot; the piece plane then moves bytes
        # over mutual TLS end to end (certify analog).
        mtls_yaml = (
            f"manager_addr: {manager_url}\nsecurity: {{auto_issue: true}}\n"
            if mtls else ""
        )
        seedcfg = write("seed.yaml", (
            "server: {host: 127.0.0.1, port: 0, advertise_ip: 127.0.0.1}\n"
            f"storage: {{dir: {tmp}/seed}}\n"
            f"piece_size: {PIECE}\n"
            + mtls_yaml
        ))
        daemon_scheduler_arg = ",".join([scheduler_url] + replica_urls)
        spawn("seed",
              ["dragonfly2_tpu.cli.dfdaemon", "--scheduler", daemon_scheduler_arg,
               "--config", seedcfg, "--seed-peer"],
              ["dfdaemon: serving"],
              {"DF_DAEMON_STATE": f"{tmp}/seed.json"})

        controls = {}
        for name, port in (("daemon-a", 0), ("daemon-b", 0)):
            dcfg = write(f"{name}.yaml", (
                "server: {host: 127.0.0.1, port: 0, advertise_ip: 127.0.0.1}\n"
                f"storage: {{dir: {tmp}/{name}}}\n"
                f"piece_size: {PIECE}\n"
                + mtls_yaml
            ))
            dout = spawn(name,
                         ["dragonfly2_tpu.cli.dfdaemon", "--scheduler",
                          daemon_scheduler_arg, "--config", dcfg],
                         ["dfdaemon: serving"],
                         {"DF_DAEMON_STATE": f"{tmp}/{name}.json"})
            controls[name] = re.search(
                r"control (\S+?)[, ]", dout["dfdaemon: serving"] + " "
            ).group(1)

        print("run_local: cluster up, running e2e loop", flush=True)
        # Ephemeral origin port: concurrent runs on one machine (CI + a
        # dev shell) must not collide on a fixed port.
        import socket as _socket

        probe = _socket.socket()
        probe.bind(("127.0.0.1", 0))
        origin_port = probe.getsockname()[1]
        probe.close()
        e2e_env = {
            **child_env("e2e"),
            "MANAGER_URL": manager_url,
            "MANAGER_URLS": manager_urls,
            "SCHEDULER_URL": scheduler_url,
            "SCHEDULER_B_URL": scheduler_b_url,
            "TRAINER_URL": trainer_url,
            "DAEMON_A_CONTROL": controls["daemon-a"],
            "DAEMON_B_CONTROL": controls["daemon-b"],
            "ORIGIN_BIND": f"127.0.0.1:{origin_port}",
            "ORIGIN_URL": f"http://127.0.0.1:{origin_port}",
        }
        rc = subprocess.call(
            [sys.executable, os.path.join(REPO, "deploy", "e2e_loop.py")],
            env=e2e_env,
        )
        print(f"run_local: e2e exit {rc}", flush=True)
        return rc
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        deadline = time.time() + 5
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    sys.exit(main())
