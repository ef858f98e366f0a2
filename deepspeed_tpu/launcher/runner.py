"""``dstpu`` CLI — the cluster launcher.

Analog of the reference ``deepspeed`` CLI (``bin/deepspeed`` →
``launcher/runner.py:317`` with hostfile parsing :157, ``--include/
--exclude`` filters :198, PDSH/MPI runners ``multinode_runner.py``) and the
per-node ``launcher/launch.py:90`` that forks one process per GPU.

TPU pods are radically simpler: ONE process per host, and JAX discovers pod
topology itself.  So the launcher's jobs reduce to:

- single host (default): exec the training script in-process env.
- multi-host emulation (``--num_processes N``): fork N local processes with
  ``DSTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`` env (the MASTER_ADDR/RANK
  analog) — used for CPU multi-process testing; refused on a host with
  TPU chips unless the children are kept off them (a chip belongs to one
  process).
- hostfile mode (``--hostfile``): ssh to each host and run the command
  there (pdsh-style fan-out, reference ``multinode_runner.py:45``) — on
  real TPU pods prefer the cloud tooling; this covers bare-metal parity.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shlex
import signal
import subprocess
import sys
import tempfile
import time
from typing import Optional

from ..utils.logging import logger

_DISCOVERY_RE = re.compile(r"^telemetry_rank(\d+)\.json$")
# per-replica serve endpoints (inference/router.py ReplicaServer):
# merged into each fleet.json entry as "serve_port" so a router can
# discover where to POST — alongside the telemetry port a FleetView
# scrapes
_SERVE_DISCOVERY_RE = re.compile(r"^serve_rank(\d+)\.json$")


def _reset_fleet_discovery(metrics_dir: Optional[str]) -> None:
    """Remove stale per-rank discovery files + ``fleet.json`` from a
    REUSED metrics dir before launching: a scraper must never route to
    last run's ports."""
    if not metrics_dir or not os.path.isdir(metrics_dir):
        return
    for fn in os.listdir(metrics_dir):
        if _DISCOVERY_RE.match(fn) or _SERVE_DISCOVERY_RE.match(fn) \
                or fn == "fleet.json":
            try:
                os.remove(os.path.join(metrics_dir, fn))
            except OSError:
                pass


def _update_fleet_discovery(metrics_dir: str, state: dict,
                            num_processes: int) -> None:
    """Aggregate the workers' ``telemetry_rank<k>.json`` files (written
    by ``telemetry/exporter.py`` once each rank's exporter BINDS — the
    only way to learn an OS-assigned ``--telemetry_port 0`` port) into
    the single ``fleet.json`` the fleet aggregator's file-discovery
    mode watches.  Rewritten (atomically) only when the replica set
    actually changes; ``state`` carries the last-written signature
    across calls."""
    entries = []
    serve_ports = {}
    try:
        names = os.listdir(metrics_dir)
    except OSError:
        return
    for fn in names:
        sm = _SERVE_DISCOVERY_RE.match(fn)
        if sm:
            try:
                with open(os.path.join(metrics_dir, fn)) as fh:
                    sdoc = json.load(fh)
                serve_ports[int(sm.group(1))] = int(sdoc["port"])
            except Exception:
                pass            # torn/partial file: pick it up next pass
            continue
        m = _DISCOVERY_RE.match(fn)
        if not m:
            continue
        try:
            with open(os.path.join(metrics_dir, fn)) as fh:
                doc = json.load(fh)
            entries.append({"rank": int(m.group(1)),
                            "host": doc["host"], "port": int(doc["port"]),
                            "pid": doc.get("pid")})
        except Exception:
            continue            # torn/partial file: pick it up next pass
    for e in entries:
        if e["rank"] in serve_ports:
            e["serve_port"] = serve_ports[e["rank"]]
    entries.sort(key=lambda e: e["rank"])
    sig = tuple((e["rank"], e["host"], e["port"], e["pid"],
                 e.get("serve_port"))
                for e in entries)
    if sig == state.get("sig"):
        return
    state["sig"] = sig
    path = os.path.join(metrics_dir, "fleet.json")
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as fh:
            json.dump({"replicas": entries,
                       "num_processes": num_processes,
                       "updated": time.time()}, fh, indent=1)
        os.replace(tmp, path)
        logger.info(f"fleet discovery: {len(entries)}/{num_processes} "
                    f"replica exporter(s) in {path}")
    except OSError as e:
        logger.warning(f"could not write fleet discovery file: {e!r}")


def _straggler_statusz(metrics_dir: Optional[str],
                       rank: int) -> Optional[str]:
    """One best-effort ``/statusz`` fetch for a lagging rank via the
    discovery file, so a straggler warning says WHAT the rank was doing
    (deep queue vs wedged loop) — not just that it is slow.  Returns a
    short annotation or None when no discovery/exporter is available."""
    if not metrics_dir:
        return None
    try:
        with open(os.path.join(metrics_dir, "fleet.json")) as fh:
            doc = json.load(fh)
        entry = next((r for r in doc.get("replicas", [])
                      if r.get("rank") == rank), None)
        if entry is None:
            return None
        import urllib.request

        with urllib.request.urlopen(
                f"http://{entry['host']}:{entry['port']}/statusz",
                timeout=0.5) as r:
            st = json.loads(r.read())
    except Exception:
        return "statusz unreachable (exporter not responding)"
    serving = st.get("serving") or {}
    goodput = st.get("goodput") or {}
    bits = ["responsive"]
    if serving:
        bits.append(f"queue_depth={serving.get('queued')}"
                    f"+{serving.get('parked')} parked")
        bits.append(f"active_slots={serving.get('active_slots')}")
    ratio = goodput.get("goodput_ratio")
    if ratio is not None:
        bits.append(f"goodput={ratio}")
    return "statusz: " + " ".join(str(b) for b in bits)


def parse_hostfile(path: str) -> dict[str, int]:
    """``hostname slots=N`` lines → {host: slots} (reference runner.py:157)."""
    hosts: dict[str, int] = {}
    with open(path) as fh:
        for line in fh:
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for p in parts[1:]:
                if p.startswith("slots="):
                    slots = int(p.split("=", 1)[1])
            hosts[host] = slots
    if not hosts:
        raise ValueError(f"hostfile {path} contains no hosts")
    return hosts


def filter_hosts(hosts: dict[str, int], include: str = "", exclude: str = "") -> dict[str, int]:
    """``--include/--exclude host1,host2`` filters (reference runner.py:198)."""
    if include:
        wanted = set(include.split(","))
        hosts = {h: s for h, s in hosts.items() if h in wanted}
    if exclude:
        dropped = set(exclude.split(","))
        hosts = {h: s for h, s in hosts.items() if h not in dropped}
    if not hosts:
        raise ValueError("host filters removed every host")
    return hosts


def _heartbeat_timeout(value: str) -> float:
    t = float(value)
    if 0 < t < 2.0:
        raise argparse.ArgumentTypeError(
            "must be >= 2s: workers throttle heartbeats to one write "
            "per second (or 0 to disable)")
    return t


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dstpu", description="DeepSpeed-TPU distributed launcher")
    p.add_argument("--hostfile", type=str, default=None)
    p.add_argument("--include", type=str, default="")
    p.add_argument("--exclude", type=str, default="")
    p.add_argument("--num_processes", type=int, default=1,
                   help="local multi-process emulation (CPU testing)")
    p.add_argument("--coordinator_port", type=int, default=7777)
    p.add_argument("--master_addr", type=str, default="127.0.0.1")
    p.add_argument("--ssh_port", type=int, default=22)
    p.add_argument("--heartbeat_timeout", type=_heartbeat_timeout,
                   default=0.0,
                   help="seconds without a worker heartbeat before the job "
                        "is declared failed (0 = detector off)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="relaunch the job this many times after a failure "
                        "(workers resume via load_checkpoint)")
    p.add_argument("--auto_resume", "--auto-resume", type=str, default=None,
                   metavar="CKPT_DIR",
                   help="resolve the newest VERIFIED checkpoint under this "
                        "dir at every (re)launch and inject "
                        "DSTPU_RESUME_DIR/DSTPU_RESUME_TAG; training "
                        "scripts pick it up via "
                        "checkpointing.maybe_auto_resume(engine).  With "
                        "--max_restarts, a crashed run resumes from the "
                        "last good checkpoint instead of step 0")
    p.add_argument("--metrics_dir", type=str, default=None,
                   help="directory for per-rank telemetry dumps: each "
                        "worker writes metrics_rank<k>.json (a registry "
                        "snapshot, see telemetry/registry.py) on exit or "
                        "SIGTERM, plus flight_<k>.json crash forensics "
                        "(telemetry/flightrec.py)")
    p.add_argument("--telemetry_port", type=int, default=None,
                   help="base port for the per-rank telemetry HTTP "
                        "exporter (/metrics /healthz /statusz, see "
                        "telemetry/exporter.py): rank k serves on port+k; "
                        "0 = OS-assigned port per rank; omit = no server")
    p.add_argument("user_script", type=str)
    p.add_argument("user_args", nargs=argparse.REMAINDER)
    return p


class HeartbeatMonitor:
    """Failure detector over per-rank heartbeat files (the reference has
    none — SURVEY.md §5 failure detection).  A worker is ``stale`` when
    its file hasn't been touched for ``timeout`` seconds; files that never
    appeared are only stale after a startup ``grace`` window (workers need
    time to reach the training loop)."""

    def __init__(self, files: list[str], timeout: float,
                 grace: Optional[float] = None):
        self.files = list(files)
        self.timeout = timeout
        self.grace = timeout * 3 if grace is None else grace
        self.t0 = time.monotonic()
        # rank -> (last seen mtime, monotonic time we OBSERVED that mtime).
        # Staleness is judged launcher-side on the monotonic clock, so an
        # NTP step or worker/launcher mtime skew can't fake a dead worker.
        self._seen: dict = {}

    def _observe(self) -> float:
        """Fold each rank's current heartbeat mtime into ``_seen`` (the
        ONE observation walk both ``stale`` and ``ages`` derive from —
        neither depends on the other being called first); returns now.

        A first sighting counts as fresh: mtime is never used as a
        clock (only compared for equality), so NTP steps or
        launcher/worker mtime skew can't fake a dead worker.  A worker
        that beat once and died pre-launch costs one extra timeout to
        flag — the safe side of that trade."""
        now = time.monotonic()
        for rank, path in enumerate(self.files):
            try:
                mtime = os.path.getmtime(path)
            except OSError:                      # not yet written
                continue
            prev = self._seen.get(rank)
            if prev is None or prev[0] != mtime:
                self._seen[rank] = (mtime, now)  # fresh beat observed
        return now

    def stale(self) -> list[int]:
        now = self._observe()
        bad = []
        for rank in range(len(self.files)):
            prev = self._seen.get(rank)
            if prev is None:
                if now - self.t0 > self.grace:
                    bad.append(rank)
            elif now - prev[1] > self.timeout:
                bad.append(rank)
        return bad

    def ages(self) -> "list[Optional[float]]":
        """Seconds since each rank's last OBSERVED beat (None = no beat
        seen yet) — the launcher-side straggler report: a rank whose age
        creeps toward the timeout is visible BEFORE it is declared dead."""
        now = self._observe()
        return [now - self._seen[r][1] if r in self._seen else None
                for r in range(len(self.files))]


_TERM_GRACE_S = 10.0    # SIGTERM → SIGKILL escalation window (lets the
                        # AsyncCheckpointManager SIGTERM-save finish)


def _resolve_auto_resume(args) -> dict:
    """``--auto_resume``: env to inject into workers naming the newest
    VERIFIED checkpoint (integrity-manifest replay — a torn or corrupt
    ``latest`` must not be handed to a fresh attempt; the worker-side
    ``maybe_auto_resume`` still walks back if storage rots between this
    resolve and the load).  Re-evaluated at every restart attempt, so
    each relaunch resumes from whatever the dying attempt managed to
    commit."""
    if not args.auto_resume:
        return {}
    from ..runtime.checkpointing import resolve_newest_verified

    resume_dir = os.path.abspath(args.auto_resume)
    try:
        tag = resolve_newest_verified(resume_dir)
    except Exception as e:
        logger.warning(f"auto-resume: resolve failed ({e!r}); fresh start")
        return {}
    if tag is None:
        logger.info(f"auto-resume: no verified checkpoint under "
                    f"{resume_dir}; fresh start")
        return {"DSTPU_RESUME_DIR": resume_dir}
    logger.info(f"auto-resume: workers will restore {tag!r} from "
                f"{resume_dir}")
    return {"DSTPU_RESUME_DIR": resume_dir, "DSTPU_RESUME_TAG": tag}


def _reap(procs, grace: float = _TERM_GRACE_S):
    """terminate → wait(grace) → kill: a worker whose SIGTERM handler
    never returns (or that is truly hung — the case heartbeat detection
    exists for) must not deadlock the launcher."""
    for pr in procs:
        if pr.poll() is None:
            pr.terminate()
    deadline = time.monotonic() + grace
    for pr in procs:
        if pr.poll() is None:
            try:
                pr.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pr.kill()
                pr.wait()


def _launch_local_procs(args, interrupted: Optional[list] = None) -> int:
    """Fork N local processes with rendezvous env (launch.py:90 analog);
    with ``--heartbeat_timeout``, watch per-rank heartbeat files and kill
    the job when a worker goes silent.  ``interrupted`` (a mutable cell)
    is set when the operator SIGINT/SIGTERMs the launcher, so the restart
    loop can tell shutdown from failure."""
    procs = []
    coord = f"{args.master_addr}:{args.coordinator_port}"
    # per-run discovery files must not survive into a reused metrics dir
    _reset_fleet_discovery(args.metrics_dir)
    hb_dir = tempfile.mkdtemp(prefix="dstpu_hb_") \
        if args.heartbeat_timeout > 0 else None
    hb_files = []
    resume_env = _resolve_auto_resume(args)
    for pid_idx in range(args.num_processes):
        env = dict(os.environ,
                   DSTPU_COORDINATOR=coord,
                   DSTPU_NUM_PROCESSES=str(args.num_processes),
                   DSTPU_PROCESS_ID=str(pid_idx),
                   **resume_env)
        if args.metrics_dir:
            env["DSTPU_METRICS_DIR"] = args.metrics_dir
        if args.telemetry_port is not None:
            # base port only: each worker offsets by its own rank
            # (telemetry/exporter.py maybe_start)
            env["DSTPU_TELEMETRY_PORT"] = str(args.telemetry_port)
        if hb_dir:
            hb = os.path.join(hb_dir, f"hb_{pid_idx}")
            env["DSTPU_HEARTBEAT_FILE"] = hb
            hb_files.append(hb)
        cmd = [sys.executable, args.user_script] + args.user_args
        logger.info(f"launching process {pid_idx}: {' '.join(map(shlex.quote, cmd))}")
        procs.append(subprocess.Popen(cmd, env=env))

    def _on_signal(signum, frame):  # operator shutdown (launch.py:176)
        if interrupted is not None:
            interrupted.append(signum)
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()

    prev_int = signal.signal(signal.SIGINT, _on_signal)
    prev_term = signal.signal(signal.SIGTERM, _on_signal)
    monitor = HeartbeatMonitor(hb_files, args.heartbeat_timeout) \
        if hb_files else None
    age_report_every = max(2.0, args.heartbeat_timeout / 2)
    last_age_report = time.monotonic()
    fleet_state: dict = {}
    last_fleet_scan = 0.0
    rc = 0
    try:
        while True:
            if args.metrics_dir \
                    and time.monotonic() - last_fleet_scan > 1.0:
                last_fleet_scan = time.monotonic()
                _update_fleet_discovery(args.metrics_dir, fleet_state,
                                        args.num_processes)
            states = [pr.poll() for pr in procs]
            if all(s is not None for s in states):
                rc = next((s for s in states if s), 0)
                break
            if any(s not in (None, 0) for s in states):
                dead = [i for i, s in enumerate(states) if s not in (None, 0)]
                logger.error(f"worker(s) {dead} exited nonzero; killing job")
                rc = next(s for s in states if s not in (None, 0))
                _reap(procs)
                break
            if monitor is not None:
                # ranks that already exited cleanly stop beating legitimately
                bad = [r for r in monitor.stale() if states[r] is None]
                if bad:
                    logger.error(f"worker(s) {bad} heartbeat stale "
                                 f"(> {args.heartbeat_timeout}s); killing job")
                    _reap(procs)
                    rc = 1
                    break
                if time.monotonic() - last_age_report > age_report_every:
                    last_age_report = time.monotonic()
                    ages = monitor.ages()
                    lagging = [
                        (r, a) for r, a in enumerate(ages)
                        if states[r] is None and a is not None
                        and a > args.heartbeat_timeout / 2]
                    if lagging:
                        # a straggler is visible BEFORE it is declared
                        # dead — and with a discovery file present, the
                        # warning says what the rank was DOING (one
                        # best-effort /statusz fetch per lagging rank).
                        # Fetches are capped at the 4 worst laggards:
                        # the monitor loop's first duty is failure
                        # DETECTION, and a fleet-wide wedge must not
                        # stall it for n_ranks x timeout while every
                        # exporter times out.
                        probe = {r for r, _ in sorted(
                            lagging, key=lambda x: -x[1])[:4]}
                        parts = []
                        for r, a in lagging:
                            ctx = _straggler_statusz(args.metrics_dir,
                                                     r) \
                                if r in probe else None
                            parts.append(
                                f"rank {r} last beat {a:.1f}s ago"
                                + (f" [{ctx}]" if ctx else ""))
                        logger.warning(
                            "heartbeat straggler(s): " + ", ".join(parts)
                            + f" (timeout {args.heartbeat_timeout}s)")
            time.sleep(0.2)
        _reap(procs)
    finally:
        # restore the caller's handlers — the launcher may be invoked
        # programmatically (restart loop, tests); leaking ours would
        # swallow the host process's Ctrl-C forever
        signal.signal(signal.SIGINT, prev_int)
        signal.signal(signal.SIGTERM, prev_term)
        if hb_dir:
            import shutil

            shutil.rmtree(hb_dir, ignore_errors=True)
    return rc


def _launch_hostfile(args) -> int:
    hosts = filter_hosts(parse_hostfile(args.hostfile), args.include, args.exclude)
    host_list = list(hosts)
    coord = f"{host_list[0]}:{args.coordinator_port}"
    procs = []
    metrics_env = f"DSTPU_METRICS_DIR={shlex.quote(args.metrics_dir)} " \
        if args.metrics_dir else ""
    if args.telemetry_port is not None:
        metrics_env += f"DSTPU_TELEMETRY_PORT={args.telemetry_port} "
    for idx, host in enumerate(host_list):
        remote_cmd = (
            f"cd {shlex.quote(os.getcwd())} && "
            f"DSTPU_COORDINATOR={coord} DSTPU_NUM_PROCESSES={len(host_list)} "
            f"DSTPU_PROCESS_ID={idx} {metrics_env}"
            f"{shlex.quote(sys.executable)} {shlex.quote(args.user_script)} "
            + " ".join(map(shlex.quote, args.user_args)))
        cmd = ["ssh", "-p", str(args.ssh_port), host, remote_cmd]
        logger.info(f"ssh launch on {host} (rank {idx})")
        procs.append(subprocess.Popen(cmd))
    rc = 0
    for pr in procs:
        pr.wait()
        rc = rc or pr.returncode
    return rc


def _report_flight_dumps(metrics_dir: Optional[str],
                         since: Optional[float] = None) -> None:
    """Pretty-print the most informative flight dump after a failure:
    dead workers' SIGTERM/excepthook handlers (telemetry/flightrec.py)
    have written their forensics by the time ``_reap`` returns, and a
    crash dump wins over the SIGTERMed bystanders'."""
    if not metrics_dir:
        return
    try:
        from ..telemetry import flightrec

        path = flightrec.newest_dump(metrics_dir, since=since)
        if path is None:
            logger.info(f"no flight dump found under {metrics_dir}")
            return
        logger.error("postmortem of the failed run:\n"
                     + flightrec.pretty(path))
    except Exception as e:   # forensics are best-effort, never fatal
        logger.warning(f"could not read flight dumps in {metrics_dir}: {e!r}")


def _disarm_own_telemetry() -> None:
    """The launcher imports ``deepspeed_tpu``, so operator-exported
    telemetry env vars (``DSTPU_TELEMETRY_PORT`` / ``DSTPU_METRICS_DIR``)
    arm the launcher PROCESS too: it would squat worker rank 0's exporter
    port and overwrite rank 0's metrics/flight dumps on exit.  Workers
    re-arm from their own (injected) env; the execv single-process path
    replaces this process image entirely, so disarming is always safe."""
    try:
        from ..telemetry import exporter, flightrec, registry

        exporter.disarm()
        flightrec.disarm()
        registry.disarm_exit_dump()
    except Exception:
        pass


_GOOGLE_PCI_VENDOR_ID = "0x1ae0"
#: set by a caller that has taken charge of which chips each process sees
_CHIP_VISIBILITY_ENVS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES")


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, read from the PCI bus — the
    launcher parent must stay off the JAX backend (a process that has
    touched it holds the chips, and its children then fail or hang)."""
    chips = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        with open(vendor) as fh:
            chips += fh.read().strip() == _GOOGLE_PCI_VENDOR_ID
    return chips


def _refuse_shared_chips(num_processes: int) -> None:
    """``--num_processes N`` is CPU multi-process emulation: nothing tells
    each child which chip is its own, so on a TPU host all N would reach
    for every chip and all but one would fail or hang.  Refuse, unless
    the environment keeps the children off the chips (``JAX_PLATFORMS``
    without ``tpu``) or the caller set the chip visibility itself."""
    if num_processes <= 1:
        return
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    if any(os.environ.get(k) for k in _CHIP_VISIBILITY_ENVS):
        return
    chips = _local_tpu_chips()
    if chips:
        raise SystemExit(
            f"dstpu: --num_processes {num_processes} on a host with "
            f"{chips} TPU chip(s): a chip belongs to one process, and the "
            f"launcher does not assign chips to its children.  Run ONE "
            f"process per host (it drives every local chip), or set "
            f"JAX_PLATFORMS=cpu for multi-process emulation, or set "
            f"{' / '.join(_CHIP_VISIBILITY_ENVS)} yourself.")


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.user_args and args.user_args[0] == "--":
        args.user_args = args.user_args[1:]
    _disarm_own_telemetry()
    if args.hostfile:
        return _launch_hostfile(args)
    _refuse_shared_chips(args.num_processes)
    if args.num_processes > 1 or args.heartbeat_timeout > 0 \
            or args.max_restarts > 0:
        # restart loop: recovery = relaunch + load_checkpoint (the
        # reference's recovery model, automated; engine resumes from the
        # `latest` tag when the script calls load_checkpoint)
        attempts = args.max_restarts + 1
        for attempt in range(attempts):
            interrupted: list = []
            attempt_t0 = time.time()
            rc = _launch_local_procs(args, interrupted)
            if rc == 0:
                return 0
            if interrupted:
                # operator shutdown (Ctrl-C / SIGTERM) is not a failure —
                # never auto-restart over the user's intent
                logger.info("job interrupted by operator; not restarting")
                return rc
            _report_flight_dumps(args.metrics_dir, since=attempt_t0)
            if attempt < attempts - 1:
                logger.warning(f"job failed (rc={rc}); restart "
                               f"{attempt + 1}/{args.max_restarts}")
        return rc
    # single process: exec in place (the common TPU case — one proc/host)
    if args.metrics_dir:
        os.environ["DSTPU_METRICS_DIR"] = args.metrics_dir
    if args.telemetry_port is not None:
        os.environ["DSTPU_TELEMETRY_PORT"] = str(args.telemetry_port)
    os.environ.update(_resolve_auto_resume(args))
    os.execv(sys.executable, [sys.executable, args.user_script] + args.user_args)


if __name__ == "__main__":
    sys.exit(main())
