#!/usr/bin/env python
"""Multi-host job launcher.

The reference launches PS-architecture jobs (scheduler + servers + workers)
through dmlc-core trackers (``tools/launch.py:13-50``, ssh/mpi/sge/yarn/
local).  On TPU there are no server processes: every host runs the same
SPMD program and gradients ride ICI/DCN collectives, so the launcher's job
shrinks to starting one identical process per host with the
``jax.distributed`` coordination env:

* ``MXTPU_COORDINATOR``  — ``host:port`` of process 0
* ``MXTPU_NUM_PROCESSES``
* ``MXTPU_PROCESS_ID``

(read by ``mxnet_tpu.kvstore.create('dist_sync_tpu')`` →
``jax.distributed.initialize``).

Launch modes:

* ``local``  — fork N processes on this machine (the reference's
  dmlc local tracker trick used by ``tests/nightly/dist_sync_kvstore.py``);
  each gets ``JAX_PLATFORMS=cpu`` and a private ``XLA_FLAGS`` virtual-device
  count so collectives are exercised without a pod.
* ``--local-elastic N`` — local mode with ELASTIC membership: a dead
  worker triggers heartbeat detection and a membership-epoch shrink
  (``mxnet_tpu.elastic``); this launcher relaunches the surviving world
  size and the job auto-resumes from its newest intact checkpoint
  (docs/how_to/multi_host.md "Elastic training").
* ``ssh``    — one process per line of ``--host-file``, same binary+args,
  envs injected over ssh (reference ssh tracker analog).
* ``gcloud`` — print (or run) the ``gcloud compute tpus tpu-vm ssh --worker=all``
  command that starts the program on every worker of a TPU pod slice, where
  JAX discovers the topology natively and no env injection is needed.
"""
import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(args, rank, num_workers, coordinator, hb_dir,
                elastic_dir=None):
    """The per-worker env contract, shared by the plain and elastic
    local launchers so it can never diverge between them."""
    env = dict(os.environ)
    env.update({
        "MXTPU_COORDINATOR": coordinator,
        "MXTPU_NUM_PROCESSES": str(num_workers),
        "MXTPU_PROCESS_ID": str(rank),
        # local mode is CPU-only by design: a chip belongs to one
        # process, and N workers on one machine would fight for it
        "JAX_PLATFORMS": "cpu",
        "TPU_SKIP_MDS_QUERY": "1",
    })
    if os.environ.get("MXTPU_HEARTBEAT_TRANSPORT", "dir") != "kv":
        # file liveness stamps for KVStore.num_dead_node; with
        # transport "kv" the stamps ride the jax.distributed
        # coordination service instead (no shared filesystem needed —
        # the multi-host default; health.py scans both)
        env["MXTPU_HEARTBEAT_DIR"] = hb_dir
    else:
        env.pop("MXTPU_HEARTBEAT_DIR", None)
    if elastic_dir is not None:
        # membership record + step barriers need the shared dir even
        # when heartbeats ride the kv transport
        env["MXTPU_ELASTIC_DIR"] = elastic_dir
        env["MXTPU_ELASTIC"] = "1"
    if args.devices_per_worker:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=%d"
                            % args.devices_per_worker)
    return env


def _run_local_once(args, allow_grace):
    """One attempt: fork N workers, tear the job down if any crashes."""
    import shutil
    import tempfile
    import time as _time
    port = _free_port()
    coordinator = "127.0.0.1:%d" % port
    hb_dir = tempfile.mkdtemp(prefix="mxtpu-hb-")
    procs = [subprocess.Popen(args.command,
                              env=_worker_env(args, rank, args.num_workers,
                                              coordinator, hb_dir))
             for rank in range(args.num_workers)]
    code = 0

    def _kill_all(signum=None, frame=None):
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, _kill_all)
    signal.signal(signal.SIGTERM, _kill_all)
    # poll all workers: one crashing must tear the job down immediately
    # (survivors block in jax.distributed.initialize waiting for peers)
    live = list(procs)
    graced = False
    try:
        while live:
            for p in list(live):
                rc = p.poll()
                if rc is None:
                    continue
                live.remove(p)
                if rc != 0:
                    code = code or rc
                    if allow_grace and not graced:
                        # grace window before teardown so survivors can
                        # observe the lapsed heartbeat (num_dead_node)
                        # and log the detection; they are parked in
                        # collectives anyway
                        graced = True
                        _time.sleep(args.detect_grace)
                    _kill_all()
            _time.sleep(0.1)
    finally:
        shutil.rmtree(hb_dir, ignore_errors=True)
    return code


def launch_local(args):
    """Local launcher with crash-restart orchestration: a failed attempt
    (a worker died) is relaunched up to ``--auto-restart`` times; workers
    resume from their checkpoints (``--load-epoch`` / auto-resume) — the
    TPU mapping of the reference's restart-aware recovery
    (``kvstore_dist.h:39-44`` ``is_recovery``; SURVEY §5: ICI failures
    are fail-stop, recovery = reload from checkpoint)."""
    attempts = args.auto_restart + 1
    for attempt in range(attempts):
        code = _run_local_once(args, allow_grace=attempt + 1 < attempts)
        if code == 0:
            return 0
        if attempt + 1 < attempts:
            print("launch.py: job failed (rc=%d); restart %d/%d" %
                  (code, attempt + 1, args.auto_restart), flush=True)
    return code


def _wait_elastic(procs, grace):
    """Wait for every worker.  The first exit (a death OR a clean
    shrink-exit) arms a straggler deadline: survivors get ``grace``
    seconds to run their own detection and exit with the shrink code;
    anything still alive after that is killed (a wedged survivor must
    not hang the orchestration).  Returns the exit codes."""
    import time as _time
    deadline = None
    while True:
        live = [p for p in procs if p.poll() is None]
        if not live:
            return [p.returncode for p in procs]
        if deadline is None and len(live) < len(procs):
            deadline = _time.monotonic() + grace
        if deadline is not None and _time.monotonic() > deadline:
            for p in live:
                p.terminate()
            for p in live:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
            return [p.returncode for p in procs]
        _time.sleep(0.1)


def launch_local_elastic(args):
    """Elastic local orchestration (``--local-elastic N``): dead-host
    detection, membership shrink, survivor relaunch, checkpoint resume.

    Each round forks the current world; mxnet_tpu.elastic inside the
    workers does the detection half (heartbeats -> membership epochs ->
    ``ElasticShrink`` -> exit ``SHRINK_EXIT_CODE``).  This loop does the
    orchestration half: when a round ends with a published shrink (or a
    dead worker), it relaunches ONLY the surviving world size — the
    relaunched job re-initializes ``jax.distributed`` over the shrunk
    world and auto-resumes from the newest intact checkpoint.  At
    success it prints ``ELASTIC_RECOVERY_S=<detect -> resumed-first-step
    seconds>`` when both timestamps were recorded."""
    import json
    import shutil
    import tempfile
    import time as _time

    # workers exiting because the membership shrank (mxnet_tpu.elastic
    # SHRINK_EXIT_CODE — mirrored here so the launcher stays importable
    # without the package)
    shrink_rc = 96
    n = args.num_workers
    edir = tempfile.mkdtemp(prefix="mxtpu-elastic-")
    detect_wall = None
    rounds = 0
    try:
        while True:
            rounds += 1
            port = _free_port()
            procs = [subprocess.Popen(
                args.command,
                env=_worker_env(args, rank, n, "127.0.0.1:%d" % port,
                                edir, elastic_dir=edir))
                for rank in range(n)]

            def _kill_all(signum=None, frame=None):
                for p in procs:
                    if p.poll() is None:
                        p.terminate()

            signal.signal(signal.SIGINT, _kill_all)
            signal.signal(signal.SIGTERM, _kill_all)
            codes = _wait_elastic(procs, args.elastic_grace)

            membership = None
            try:
                with open(os.path.join(edir, "membership.json")) as f:
                    membership = json.load(f)
            except (OSError, ValueError):
                pass
            if all(c == 0 for c in codes):
                status = None
                try:
                    with open(os.path.join(edir,
                                           "resume-status.json")) as f:
                        status = json.load(f)
                except (OSError, ValueError):
                    pass
                if detect_wall is not None and status \
                        and status.get("first_step_wall"):
                    print("ELASTIC_RECOVERY_S=%.2f"
                          % (status["first_step_wall"] - detect_wall),
                          flush=True)
                print("launch.py: elastic job complete (world=%d after "
                      "%d round(s))" % (n, rounds), flush=True)
                return 0
            if membership is not None and membership.get("epoch", 1) > 1 \
                    and len(membership.get("world", [])) < n:
                new_n = len(membership["world"])
                detect_wall = membership.get("wallclock") or _time.time()
                print("launch.py: membership epoch %d — dead=%s; "
                      "shrinking %d -> %d and relaunching survivors"
                      % (membership["epoch"], membership.get("dead"),
                         n, new_n), flush=True)
            else:
                # no published shrink (e.g. every worker died before a
                # survivor could publish): drop the ranks that failed
                dead = sum(1 for c in codes if c not in (0, shrink_rc))
                new_n = n - dead
                detect_wall = _time.time()
                print("launch.py: %d worker(s) died without a published "
                      "shrink (codes=%s); relaunching %d"
                      % (dead, codes, new_n), flush=True)
            if new_n < 1 or new_n >= n:
                code = next((c for c in codes if c != 0), 1)
                print("launch.py: elastic job failed (codes=%s)" % codes,
                      flush=True)
                return code
            n = new_n
            # fresh coordination state for the new incarnation: stale
            # heartbeat/barrier stamps and the old-world membership must
            # not leak into the relaunched job (the relaunch assigns new
            # contiguous ranks)
            for name in os.listdir(edir):
                try:
                    os.remove(os.path.join(edir, name))
                except OSError:
                    pass
    finally:
        shutil.rmtree(edir, ignore_errors=True)


def launch_ssh(args):
    with open(args.host_file) as f:
        hosts = [h.strip() for h in f if h.strip() and
                 not h.startswith("#")]
    coordinator = "%s:%d" % (hosts[0].split()[0], args.port)
    procs = []
    for rank, host in enumerate(hosts):
        envs = ("MXTPU_COORDINATOR=%s MXTPU_NUM_PROCESSES=%d "
                "MXTPU_PROCESS_ID=%d" % (coordinator, len(hosts), rank))
        cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host,
               "cd %s; %s %s" % (args.remote_dir or "~", envs,
                                 " ".join(args.command))]
        procs.append(subprocess.Popen(cmd))
    code = 0
    for p in procs:
        p.wait()
        code = code or p.returncode
    return code


def launch_gcloud(args):
    cmd = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", args.tpu_name,
           "--zone", args.zone, "--worker=all",
           "--command", " ".join(args.command)]
    print(" ".join(cmd))
    if args.dry_run:
        return 0
    return subprocess.call(cmd)


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job")
    parser.add_argument("-n", "--num-workers", type=int, default=1,
                        help="number of processes (local mode)")
    parser.add_argument("--launcher", choices=["local", "ssh", "gcloud"],
                        default="local",
                        help="local (and --local-elastic) workers run on "
                             "the CPU only: each gets JAX_PLATFORMS=cpu, "
                             "because one process holds a chip")
    parser.add_argument("--devices-per-worker", type=int, default=0,
                        help="local mode: virtual CPU devices per process")
    parser.add_argument("--auto-restart", type=int, default=0,
                        help="local mode: relaunch the job up to N times "
                        "after a worker crash (workers resume from their "
                        "checkpoints)")
    parser.add_argument("--detect-grace", type=float, default=5.0,
                        help="auto-restart mode: seconds between a worker "
                        "crash and job teardown, letting survivors log "
                        "num_dead_node detection")
    parser.add_argument("--local-elastic", type=int, default=0,
                        metavar="N",
                        help="elastic local mode: N workers with "
                        "membership-epoch shrink — a dead worker is "
                        "detected via heartbeats, survivors exit at the "
                        "batch boundary, and the job relaunches at the "
                        "shrunk world size, resuming from the newest "
                        "intact checkpoint (docs/how_to/multi_host.md)")
    parser.add_argument("--elastic-grace", type=float, default=90.0,
                        help="elastic mode: seconds survivors get, after "
                        "the first worker exit, to run their own "
                        "detection and exit before being killed")
    parser.add_argument("-H", "--host-file", default=None,
                        help="ssh mode: one host per line")
    parser.add_argument("--port", type=int, default=9000,
                        help="ssh mode: coordinator port on host[0]")
    parser.add_argument("--remote-dir", default=None)
    parser.add_argument("--tpu-name", default=None, help="gcloud mode")
    parser.add_argument("--zone", default="us-central1-a")
    parser.add_argument("--dry-run", action="store_true")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="program and args to run on every worker")
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.command[0] == "--":
        args.command = args.command[1:]
    if args.local_elastic:
        args.num_workers = args.local_elastic
        sys.exit(launch_local_elastic(args))
    if args.launcher == "local":
        sys.exit(launch_local(args))
    elif args.launcher == "ssh":
        if not args.host_file:
            parser.error("--host-file required for ssh launcher")
        sys.exit(launch_ssh(args))
    else:
        if not args.tpu_name:
            parser.error("--tpu-name required for gcloud launcher")
        sys.exit(launch_gcloud(args))


if __name__ == "__main__":
    main()
