"""Traffic mode ``open_loop_served``: one ``trivy-tpu server`` in this
process, owning the chip, and thin clients in child processes that
push each new build's layer analyses over ``127.0.0.1`` in an open
loop. Parameters in the traffic file: ``rate_per_s`` (mean arrivals a
second), ``arrival_gamma_shape``, ``bases``, ``base_zipf_s``,
``schedule_factor`` (the schedule holds that many times what a window
at the rate sends, and is never wrapped), ``clients`` (child
processes), ``warmup``, ``memo`` (``default``: the server
command's), ``sched``, ``security_checks``.

The server is built by the function the ``server`` command calls
(``trivy_tpu.rpc.server.build_server``: scheduler, memo, cache and
the warm of the interval ladder) and listens on a port of its own;
the harness's runner is re-seated on that server's scheduler, so the
counters the metrics read are those of the scheduler that serves the
RPCs, and nothing reaches it except through the RPC. A program
without that function cannot run the cell and says so at once.

A client is this file run as a script: a CI runner of the
organisation. In the set-up it writes its share of the builds
(``gen_served``) and has the program's own client side analyze each
once (``ImageArtifact.inspect`` against a ``MemoryCache``, which
keeps what it was given), so the window replays pushes and analyzes
nothing. In the window each build is sent at its time **whether or
not earlier ones have finished**, a thread a build, through
``trivy_tpu.rpc.client`` (its headers, its retry and backoff):
``MissingBlobs``, ``PutBlob`` for what is missing, ``PutArtifact``,
``Scan``. The window ends with the first completion at or after
``--seconds``; a unit is a Scan answered in it. Clients and harness
talk in JSON lines over pipes; ``time.monotonic`` is one clock for
all processes of a machine.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH), BENCH):  # a client starts here
    if _p not in sys.path:
        sys.path.insert(0, _p)

try:
    from trivy_tpu.rpc.server import build_server, serve
except ImportError as e:        # run.py: "cannot run", exit 3
    raise ImportError(
        "this program has no trivy_tpu.rpc.server.build_server, the "
        f"function the served cell builds its server with ({e})"
    ) from e

LEAD_S = 1.0            # from "go" to the schedule's zero
DRAIN_S = 120.0         # the queue at the window's end may take this
RENDER_KEEP = 300       # reports rendered and compared at most
SETTLE_S = 0.05         # completion lines of one instant, all in


# ---------------------------------------------------------------------
# the harness's side
# ---------------------------------------------------------------------

class Client:
    """One child process and the thread that reads its lines."""

    def __init__(self, index: int):
        self.index = index
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True)
        self.replies: list = []
        self.cv = threading.Condition()
        self.on_done = None         # called with a completion record
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            msg = json.loads(line)
            if "k" in msg and self.on_done is not None:
                self.on_done(msg)
                continue
            with self.cv:
                self.replies.append(msg)
                self.cv.notify_all()
        with self.cv:
            self.replies.append({"eof": self.proc.wait()})
            self.cv.notify_all()

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def reply(self, timeout: float) -> dict:
        with self.cv:
            if not self.cv.wait_for(lambda: self.replies, timeout):
                raise RuntimeError(f"client {self.index}: no reply "
                                   f"in {timeout:.0f} s")
            msg = self.replies.pop(0)
        if "eof" in msg or "error" in msg:
            raise RuntimeError(f"client {self.index}: {msg}")
        return msg

    def close(self) -> None:
        try:
            self.send(cmd="quit")
            self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()


def make_data(cell, work: str) -> dict:
    """The schedule, and the clients with their builds written and
    analyzed."""
    import gen_served
    t = cell.traffic
    n = math.ceil(t["schedule_factor"] * t["rate_per_s"]
                  * cell.seconds)
    plan = gen_served.plan(t, n, cell.seed)
    clients = [Client(i) for i in range(t["clients"])]
    share: list = [[] for _ in clients]
    for k in range(n):
        share[k % len(clients)].append(
            [k, plan["base_of"][k], plan["at"][k]])
    # the warm-up's builds sit on a base the window never asks for
    warm = [[n + j, t["bases"], 0.0] for j in range(t["warmup"])]
    try:
        for c, builds in zip(clients, share):
            c.send(cmd="make", sizes=cell.sizes, seed=cell.seed,
                   directory=work, builds=builds,
                   warm=warm if c.index == 0 else [],
                   checks=t["security_checks"])
        facts: dict = {}
        for c in clients:
            for f in c.reply(600.0)["facts"]:
                facts[f["build"]] = f
    except BaseException:
        for c in clients:
            c.close()
        raise
    first: dict = {}
    for k in range(n):
        first.setdefault(plan["base_of"][k], facts[k])
    return {"clients": clients, "facts": facts, "plan": plan,
            "n": n, "first": first, "work": work}


def warm_up(cell, data: dict) -> list:
    """The server, built and warmed as the command builds it, and
    the warm-up's builds through the four RPCs."""
    from trivy_tpu.runtime import BatchScanRunner
    t = cell.traffic
    server = build_server(
        store=cell.runner.store, sched=t["sched"],
        cache_dir=os.path.join(data["work"], "server-cache"),
        memo=t["memo"] == "default")
    httpd, _ = serve("127.0.0.1", 0, server)
    data["server"], data["httpd"] = server, httpd
    data["url"] = f"http://127.0.0.1:{httpd.server_address[1]}"
    # run.py's counters are read from its runner's scheduler: seat
    # the runner on the scheduler that serves the RPCs
    cell.runner = BatchScanRunner(
        store=cell.runner.store, backend="tpu",
        sched=server.scheduler)
    if server.health()["status"] != "ok":
        return [f"server not ready: {server.health()}"]
    c = data["clients"][0]
    c.send(cmd="warm", url=data["url"])
    return c.reply(600.0)["bad"]


def percentile(values: list, q: float) -> float:
    if not values:
        return float("nan")
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


def drive(cell, data: dict, seconds: float) -> dict:
    clients = data["clients"]
    done: list = []
    cv = threading.Condition()

    def on_done(msg):
        with cv:
            done.append(msg)
            cv.notify_all()

    rpc0 = data["server"].rpc.snapshot()
    t0 = time.monotonic() + LEAD_S
    for c in clients:
        c.on_done = on_done
        c.send(cmd="go", url=data["url"], t0=t0)
    with cv:
        # (a schedule that runs out ends the window with its last
        # answer: the cell's is half again as long as a window)
        cv.wait_for(lambda: len(done) == data["n"]
                    or any(m["done"] - t0 >= seconds for m in done),
                    timeout=LEAD_S + seconds + DRAIN_S)
    time.sleep(SETTLE_S)
    for c in clients:
        c.send(cmd="stop")
    sent = [k for c in clients for k in c.reply(30.0)["sent"]]
    rpc1 = data["server"].rpc.snapshot()
    with cv:
        past = [m["done"] - t0 for m in done
                if m["done"] - t0 >= seconds]
        window_s = min(past) if past else max(
            (m["done"] - t0 for m in done), default=seconds)
        inside = [m for m in done if m["done"] - t0 <= window_s]
    data["done"], data["cv"] = done, cv
    answered = {m["k"] for m in inside}
    ok = [m for m in inside if m["ok"]]
    late_ms = [(m["start"] - m["at"]) * 1e3 for m in inside]
    took_ms = [(m["done"] - m["at"]) * 1e3 for m in inside]
    rec = {"finished": [(m["k"], m) for m in inside],
           "in_flight": [(k, None) for k in sent
                         if k not in answered],
           "units": len(ok), "window_s": window_s, "wraps": 0}
    backlog = len(rec["in_flight"])
    rate = len(ok) / window_s if window_s else 0.0
    rec["latency_ms"] = {f"p{q}": percentile(took_ms, q / 100)
                         for q in (50, 95, 99)}
    rec["lateness_ms"] = {"p50": percentile(late_ms, 0.5),
                          "p99": percentile(late_ms, 0.99),
                          "worst": max(late_ms, default=0.0)}
    p99 = rec["lateness_ms"]["p99"]
    rec["lines"] = [
        f"arrivals: {len(sent)} sent in {window_s:.3f} s "
        f"({len(sent) / window_s:.3f}/s offered, the schedule's "
        f"mean {cell.traffic['rate_per_s']}/s), {len(ok)} answered "
        f"ok ({rate:.3f}/s), {len(inside) - len(ok)} answered not "
        f"ok, backlog at the window's end {backlog} builds "
        f"({backlog / rate if rate else float('inf'):.2f} s of "
        "work)",
        f"shed: {rpc1['shed_503'] - rpc0['shed_503']} Scans answered "
        f"503, {rpc1['retried'] - rpc0['retried']} came again; the "
        "clients retried "
        f"{sum(m['retries'] for m in inside)} calls of the builds "
        "answered",
        "generator lateness (ms, scheduled to its first call): "
        + " ".join(f"{q} {v:.3f}"
                   for q, v in rec["lateness_ms"].items())
        + ("" if p99 < 5.0 else
           f" (p99 over 5 ms by {p99 - 5.0:.3f})"),
        "client latency (ms, scheduled to answered): "
        + " ".join(f"{q} {v:.1f}"
                   for q, v in rec["latency_ms"].items())]
    return rec


class Answer:
    """What ``check.compare`` reads of a slot."""

    def __init__(self, status: str, error: str = "", report=None):
        self.status, self.error, self.report = status, error, report


def report_of(name: str, body: dict):
    """The client's report of a Scan's answer, as ``cli.run_image``
    makes it from what ``RemoteScanner.scan`` returns."""
    from trivy_tpu.types import Metadata, Report
    from trivy_tpu.types.convert import (os_from_dict,
                                         result_from_dict)
    return Report(
        artifact_name=name, artifact_type="container_image",
        metadata=Metadata(os=os_from_dict(body["os"])),
        results=[result_from_dict(r) for r in body["results"]])


def answers(cell, rec: dict, data: dict) -> dict:
    """Every build answered in the window or in flight at its end
    (waited for) against the reference: by its count of findings,
    which every answer carries, and, for up to ``RENDER_KEEP`` of
    them, rendered by the program's json writer and compared whole:
    first every base's first sight, every build in flight at the
    window's end, every build a call of which was retried and every
    build whose count is not the reference's, then positions drawn
    from the seed."""
    import numpy as np

    import reference_served as reference
    facts, checks = data["facts"], cell.traffic["security_checks"]
    clients, done, cv = data["clients"], data["done"], data["cv"]
    waiting = {k for k, _ in rec["in_flight"]}
    with cv:
        cv.wait_for(lambda: waiting <= {m["k"] for m in done},
                    timeout=DRAIN_S)
        by_k = {m["k"]: m for m in done}
    never = sorted(waiting - set(by_k))
    for k in never:
        cell.say(f"never answered: build {k}")
    took = [k for k, _ in rec["finished"]] + sorted(
        waiting - set(never))
    want = {k: reference.build_findings(cell.table, facts[k], checks)
            for k in took}
    must = [k for k in took
            if data["plan"]["first"][k] or k in waiting
            or by_k[k]["retries"] or not by_k[k]["ok"]
            or by_k[k]["vulns"] != len(want[k]["vulns"])]
    rest = sorted(set(took) - set(must))
    rng = np.random.default_rng([cell.seed, 8])
    drawn = rng.choice(rest, min(len(rest), max(
        0, RENDER_KEEP - len(must))), replace=False) \
        if rest else []
    keep = sorted(must[:RENDER_KEEP] + [int(k) for k in drawn])
    cell.say(f"reports: {len(keep)} of {len(took)} rendered and "
             f"compared, {len(must)} of them first sights, in "
             "flight, retried or of another count than the "
             "reference's, the rest drawn from the seed")
    bodies: dict = {}
    for c in clients:
        c.send(cmd="fetch", ks=[k for k in keep
                                if k % len(clients) == c.index])
    for c in clients:
        bodies.update((int(k), v)
                      for k, v in c.reply(120.0)["answers"].items())
    out = []
    for k in keep:
        m, name = by_k[k], f"build{k}"
        res = Answer("ok", report=report_of(name, bodies[k])) \
            if m["ok"] else Answer("failed", m["error"])
        out.append((name, res, want[k], reference.build_findings(
            cell.table, facts[k], checks, control=True,
            first=data["first"][facts[k]["base"]])
            if cell.control else None))
    # the interval jobs the window cannot have done without: cache
    # and memo held the warm-up's base alone when it opened, so each
    # distinct job of what finished went to the device at least once
    jobs: set = set()
    for k, m in rec["finished"]:
        if m["ok"]:
            jobs |= reference.build_jobs(cell.table, facts[k])
    close(data)
    return {"answers": out, "never": len(never),
            "expected_rows": len(jobs)}


def close(data: dict) -> None:
    """Clients, listener, server. The scheduler's counters stay
    readable after it."""
    for c in data["clients"]:
        c.close()
    data["httpd"].shutdown()
    data["httpd"].server_close()
    data["server"].shutdown_gracefully(10.0)


# ---------------------------------------------------------------------
# a client's side: this file run as a script
# ---------------------------------------------------------------------

def client_main() -> None:
    sys.setswitchinterval(0.0005)   # a dispatcher that wakes on time
    import gen_served
    from trivy_tpu.artifact import ImageArtifact, load_image
    from trivy_tpu.artifact.artifact import ArtifactOption
    from trivy_tpu.artifact.cache import MemoryCache
    from trivy_tpu.rpc.client import RemoteCache, RemoteScanner
    from trivy_tpu.scan.local import ScanTarget
    from trivy_tpu.types import ScanOptions

    out_lock = threading.Lock()

    def say(**msg) -> None:
        with out_lock:
            sys.stdout.write(json.dumps(msg) + "\n")
            sys.stdout.flush()

    mine = MemoryCache()            # keeps what inspect gave it
    refs: dict = {}                 # build -> (name, id, blob ids)
    kept: dict = {}                 # build -> the Scan's answer
    state = {"opts": None, "schedule": [], "warm": []}
    stop = threading.Event()
    sent: list = []

    def make(cmd: dict) -> list:
        sizes, seed = cmd["sizes"], cmd["seed"]
        state["opts"] = ScanOptions(
            backend="tpu", security_checks=list(cmd["checks"]))
        state["schedule"] = [(k, at) for k, _b, at in cmd["builds"]]
        state["warm"] = [k for k, _b, _at in cmd["warm"]]
        # a thin client with --security-checks vuln: no secret scan
        option = ArtifactOption(scan_secrets=False)
        bases, facts = {}, []
        for k, b, _at in cmd["builds"] + cmd["warm"]:
            if b not in bases:
                bases[b] = gen_served.build_base(sizes, b, seed)
            path = os.path.join(cmd["directory"], f"build{k}.tar")
            f = gen_served.build_top(sizes, k, b, bases[b], path,
                                     seed)
            ref = ImageArtifact(load_image(path, name=f"build{k}"),
                                mine, option=option).inspect()
            os.unlink(path)
            refs[k] = (ref.name, ref.id, ref.blob_ids)
            facts.append(f)
        return facts

    def push(url: str, k: int, at: float) -> dict:
        """One build: the four RPCs in a ``--server`` client's
        order, nothing analyzed. Returns the completion's line."""
        name, artifact_id, blob_ids = refs[k]
        cache, scanner = RemoteCache(url), RemoteScanner(url)
        msg = {"k": k, "at": at, "start": time.monotonic(),
               "ok": False, "error": "", "vulns": 0}
        try:
            missing_artifact, missing = cache.missing_blobs(
                artifact_id, blob_ids)
            for b in blob_ids:
                if b in missing:
                    cache.put_blob(b, mine.blobs[b])
            if missing_artifact:
                cache.put_artifact(artifact_id,
                                   mine.artifacts[artifact_id])
            results, os_found = scanner.scan(
                ScanTarget(name=name, artifact_id=artifact_id,
                           blob_ids=blob_ids), state["opts"])
            kept[k] = {"os": os_found.to_dict() if os_found
                       else None,
                       "results": [r.to_dict() for r in results]}
            msg["ok"] = True
            msg["vulns"] = sum(len(r.vulnerabilities)
                               for r in results)
        except Exception as e:      # noqa: BLE001 (the slot's error)
            msg["error"] = repr(e)[:300]
        msg["done"] = time.monotonic()
        msg["retries"] = cache.counters["retries"] \
            + scanner.counters["retries"]
        return msg

    def dispatch(url: str, t0: float) -> None:
        for k, at in state["schedule"]:
            if stop.wait(max(0.0, t0 + at - time.monotonic())):
                return
            sent.append(k)
            threading.Thread(
                target=lambda k=k, at=at: say(**push(url, k,
                                                     t0 + at)),
                daemon=True).start()

    for line in sys.stdin:
        cmd = json.loads(line)
        try:
            if cmd["cmd"] == "make":
                say(facts=make(cmd))
            elif cmd["cmd"] == "warm":
                took = [push(cmd["url"], k, time.monotonic())
                        for k in state["warm"]]
                kept.clear()
                say(bad=[f"build{m['k']}: {m['error']}"
                         for m in took if not m["ok"]])
            elif cmd["cmd"] == "go":
                threading.Thread(
                    target=dispatch, args=(cmd["url"], cmd["t0"]),
                    daemon=True).start()
            elif cmd["cmd"] == "stop":
                stop.set()
                say(sent=list(sent))
            elif cmd["cmd"] == "fetch":
                say(answers={str(k): kept.pop(k)
                             for k in cmd["ks"] if k in kept})
                kept.clear()
            elif cmd["cmd"] == "quit":
                return
        except Exception as e:      # noqa: BLE001 (the harness's)
            say(error=repr(e)[:500])


if __name__ == "__main__":
    client_main()
