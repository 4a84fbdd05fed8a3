#!/usr/bin/env python
"""Distributed job launcher (reference ``tools/launch.py`` + dmlc
tracker [path cites — unverified]).

Reference protocol: 1 scheduler + S servers + W workers wired via
DMLC_* env vars. TPU-native: W equal processes rendezvous at a
jax.distributed coordinator; the DMLC_* names are kept so reference
invocations port verbatim:

    python tools/launch.py -n 4 --launcher local python train.py

Launchers: local (fork N processes on this host) and ssh (one process
per host from --host-file). A chip belongs to one process at a time
and one process drives every chip of its host through the mesh, so
``local`` with more than one worker is a CPU rehearsal of the
multi-process protocol: on a host with chips it refuses unless the
workers are pinned to the CPU (``--env JAX_PLATFORMS=cpu``).
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _refuse_shared_chips(env, n_workers):
    """Exit when ``n_workers`` local workers started with ``env`` would
    all open this host's chips. What jax would run on is asked of a
    short-lived child — the launcher never imports jax itself, or it
    would hold the chip its workers need."""
    if n_workers < 2 or env.get("JAX_PLATFORMS", "").startswith("cpu"):
        return
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        env=env, capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        raise SystemExit("launch.py: cannot tell which devices the "
                         f"workers would open:\n{probe.stderr[-2000:]}")
    platform = probe.stdout.split()[-1]
    if platform != "cpu":
        raise SystemExit(
            f"launch.py: refusing to start {n_workers} local workers: "
            f"each would open every {platform} chip of this host, and "
            "a chip belongs to one process at a time (the others fail "
            "or hang). One process drives all of a host's chips "
            "through the mesh (mxtpu.parallel.create_mesh); to "
            "rehearse the multi-process protocol here, pin the workers "
            "to the CPU with --env JAX_PLATFORMS=cpu.")


def launch_local(args, command):
    port = args.port or _free_port()
    envs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_NUM_SERVER": str(args.num_servers),
        })
        if args.env:
            for kv in args.env:
                k, _, v = kv.partition("=")
                env[k] = v
        envs.append(env)
    _refuse_shared_chips(envs[0], args.num_workers)
    procs = [subprocess.Popen(command, env=env) for env in envs]
    code = 0

    def _kill(*_):
        for p in procs:
            p.terminate()

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    # poll all workers: a crashed rank must take the job down, not hang
    # the survivors inside the rendezvous
    import time
    live = list(procs)
    while live:
        for p in list(live):
            rc = p.poll()
            if rc is not None:
                live.remove(p)
                code = code or rc
                if rc != 0:
                    for q in live:
                        q.terminate()
        time.sleep(0.2)
    return code


def launch_ssh(args, command):
    with open(args.host_file) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < args.num_workers:
        raise SystemExit(f"need {args.num_workers} hosts, have "
                         f"{len(hosts)} in {args.host_file}")
    port = args.port or 9091
    coord = hosts[0]
    procs = []
    secret = os.environ.get("MXTPU_PS_SECRET")
    for rank in range(args.num_workers):
        envs = " ".join([
            f"DMLC_ROLE=worker",
            f"DMLC_PS_ROOT_URI={coord}",
            f"DMLC_PS_ROOT_PORT={port}",
            f"DMLC_NUM_WORKER={args.num_workers}",
            f"DMLC_WORKER_ID={rank}",
        ] + (args.env or []))
        cmd = f"cd {os.getcwd()} && {envs} {' '.join(command)}"
        if secret:
            # The shared secret must never appear on a command line —
            # ps / /proc/<pid>/cmdline are world-readable on both the
            # launching and remote hosts, which would defeat the HMAC
            # peer auth it exists for. The remote shell reads it from
            # ssh's stdin instead: $(cat) slurps to EOF (multi-line
            # secrets survive; only trailing newlines are stripped),
            # and an empty read aborts loudly rather than starting the
            # worker unauthenticated.
            cmd = ("MXTPU_PS_SECRET=$(cat) && "
                   "[ -n \"$MXTPU_PS_SECRET\" ] || "
                   "{ echo 'launch.py: no secret on stdin' >&2; "
                   "exit 90; }; export MXTPU_PS_SECRET; " + cmd)
            proc = subprocess.Popen(["ssh", hosts[rank], cmd],
                                    stdin=subprocess.PIPE)
            try:
                proc.stdin.write(secret.encode())
                proc.stdin.close()
            except BrokenPipeError:
                pass   # ssh died before reading (unreachable host):
                       # its nonzero exit is reported by the wait loop
        else:
            proc = subprocess.Popen(["ssh", hosts[rank], cmd])
        procs.append(proc)
    code = 0
    for p in procs:
        p.wait()
        code = code or p.returncode
    return code


def _dmlc_wrapper(rank_expr, args, coord, port):
    """The bash prologue exporting the DMLC env protocol with the
    worker id taken from ``rank_expr`` (scheduler-specific env var).
    Shared by mpi/slurm so the tested code IS the shipped code; all
    values are shell-quoted."""
    import shlex
    exports = [
        "export DMLC_ROLE=worker",
        f"export DMLC_PS_ROOT_URI={shlex.quote(str(coord))}",
        f"export DMLC_PS_ROOT_PORT={shlex.quote(str(port))}",
        f"export DMLC_NUM_WORKER={args.num_workers}",
        f"export DMLC_WORKER_ID={rank_expr}",
    ]
    # MXTPU_PS_SECRET is deliberately NOT exported here: the wrapper
    # string becomes a bash -c argv (visible in ps), so the secret
    # rides the scheduler's native env forwarding instead (mpirun -x /
    # srun --export), which passes names, not values.
    for e in (args.env or []):
        k, _, v = e.partition("=")
        exports.append(f"export {k}={shlex.quote(v)}")
    return "; ".join(exports) + '; exec "$@"'


def launch_mpi(args, command):
    """mpirun-backed launch (reference dmlc_tracker/mpi.py): one rank
    per worker; DMLC_* derived from OMPI/PMI rank vars by a wrapper."""
    port = args.port or 9091
    coord = os.environ.get("MXTPU_COORD_HOST", "127.0.0.1")
    wrapper = _dmlc_wrapper(
        "${OMPI_COMM_WORLD_RANK:-${PMI_RANK:-0}}", args, coord, port)
    cmd = ["mpirun", "-np", str(args.num_workers)]
    if os.environ.get("MXTPU_PS_SECRET"):
        cmd += _mpi_env_forward_flags()    # name only; value stays env
    cmd += ["bash", "-c", wrapper, "--"] + list(command)
    return subprocess.call(cmd)


def _mpi_env_forward_flags():
    """Env-forwarding flags for the detected MPI flavor (the flag that
    passes a variable NAME, keeping the value out of argv): OpenMPI
    wants ``-x``; MPICH/Hydra and Intel MPI want ``-genvlist``. An
    unrecognizable mpirun FAILS CLOSED — launching ranks silently
    unauthenticated would undo the protection the secret exists for
    (the ssh path's `exit 90` is the same policy)."""
    try:
        ver = subprocess.run(["mpirun", "--version"],
                             capture_output=True, text=True,
                             timeout=10).stdout
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SystemExit(
            f"launch.py: cannot probe mpirun --version ({e}); refusing "
            "to launch with MXTPU_PS_SECRET set but not forwardable. "
            "Unset the secret or use a launcher with known env "
            "forwarding (ssh/slurm).")
    if "Open MPI" in ver or "OpenRTE" in ver:
        return ["-x", "MXTPU_PS_SECRET"]
    if "HYDRA" in ver or "MPICH" in ver or "Intel" in ver:
        return ["-genvlist", "MXTPU_PS_SECRET"]
    raise SystemExit(
        "launch.py: unrecognized MPI flavor (mpirun --version says: "
        f"{ver.splitlines()[:1]}); refusing to launch with "
        "MXTPU_PS_SECRET set — it would not reach the workers. Use "
        "your scheduler's env forwarding or the ssh launcher.")


def launch_slurm(args, command):
    """srun-backed launch (reference dmlc_tracker/slurm.py)."""
    port = args.port or 9091
    coord = os.environ.get("MXTPU_COORD_HOST",
                           os.environ.get("SLURM_LAUNCH_NODE_IPADDR",
                                          "127.0.0.1"))
    wrapper = _dmlc_wrapper("${SLURM_PROCID:-0}", args, coord, port)
    cmd = ["srun", f"--ntasks={args.num_workers}", "--export=ALL",
           "bash", "-c", wrapper, "--"] + list(command)
    return subprocess.call(cmd)


def launch_sge(args, command):
    """SGE array-job launch (reference dmlc_tracker/sge.py): emits a
    qsub script; DMLC_WORKER_ID = SGE_TASK_ID - 1."""
    port = args.port or 9091
    coord = os.environ.get("MXTPU_COORD_HOST", "127.0.0.1")
    import shlex
    env_lines = []
    for e in (args.env or []):
        k, _, v = e.partition("=")
        env_lines.append(f"export {k}={shlex.quote(v)}")
    script = "\n".join([
        "#!/bin/bash",
        f"#$ -t 1-{args.num_workers}",
        "#$ -cwd",
        "export DMLC_ROLE=worker",
        f"export DMLC_PS_ROOT_URI={shlex.quote(str(coord))}",
        f"export DMLC_PS_ROOT_PORT={port}",
        f"export DMLC_NUM_WORKER={args.num_workers}",
        "export DMLC_WORKER_ID=$((SGE_TASK_ID - 1))",
    ] + env_lines +
        [" ".join(shlex.quote(c) for c in command), ""])
    path = os.path.abspath("mxtpu_sge_job.sh")
    with open(path, "w") as f:
        f.write(script)
    print(f"wrote {path}; submit with: qsub {path}")
    return 0


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="accepted for reference CLI parity (the "
                        "all-reduce design has no server role)")
    p.add_argument("--launcher",
               choices=["local", "ssh", "mpi", "slurm", "sge"],
               default="local")
    p.add_argument("-H", "--host-file", help="hosts for --launcher ssh")
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--env", nargs="*", help="extra KEY=VALUE to export")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        raise SystemExit("no command given")
    launchers = {"local": launch_local, "ssh": launch_ssh,
                 "mpi": launch_mpi, "slurm": launch_slurm,
                 "sge": launch_sge}
    sys.exit(launchers[args.launcher](args, args.command))


if __name__ == "__main__":
    main()
