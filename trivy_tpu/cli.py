"""Command-line interface (reference: pkg/commands/app.go).

Subcommands mirror the reference's cobra tree: image, filesystem
(alias fs), rootfs, sbom, db build, version — flags follow the same
names so invocations port over (``--severity``, ``--security-checks``,
``--format``, ``--ignore-unfixed``, ``--skip-dirs`` …), plus
``--backend tpu|cpu|cpu-ref`` selecting the kernel dispatch path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from tarfile import TarError as tarfile_error

# opt-in runtime lock-order witness (docs/static-analysis.md):
# TRIVY_TPU_LOCK_WITNESS=1 must install BEFORE the heavy submodule
# imports below construct the metric-singleton locks
# (DETECT/RING/SECRET_METRICS...), matching the test conftest's
# install-before-any-import order — witness.py itself imports only
# os/sys/threading
from .analysis.witness import maybe_install_from_env

maybe_install_from_env()

from . import __version__  # noqa: E402
from .artifact import (ArtifactOption, FSCache, ImageArtifact,
                       LocalFSArtifact, load_image)
from .db import AdvisoryStore, load_fixtures
from .report import write_report
from .scan import LocalScanner, ScanTarget, filter_results
from .scan.filter import load_ignore_file
from .types import (Metadata, Report, ScanOptions, Severity,
                    SEVERITIES)

DEFAULT_SEVERITIES = "UNKNOWN,LOW,MEDIUM,HIGH,CRITICAL"


def _admission_flags(sp) -> None:
    """K8s validating-admission webhook knobs (docs/serving.md
    'Continuous scanning & admission control') — shared by the
    server and the watch command (both mount POST /k8s/admission)."""
    sp.add_argument("--admission-policy", default="deny:CRITICAL",
                    help="severity policy for POST /k8s/admission: "
                    "'deny:SEV[,SEV...]' denies pods whose images "
                    "carry findings at those severities; 'audit' "
                    "never denies (annotations only)")
    sp.add_argument("--admission-fail", default="open",
                    choices=["open", "closed", "408"],
                    help="stance when a verdict cannot resolve "
                    "inside the deadline: open = allow + annotate, "
                    "closed = deny, 408 = surface HTTP 408 and let "
                    "the webhook's K8s failurePolicy decide")
    sp.add_argument("--admission-deadline", type=float, default=10.0,
                    help="default verdict deadline in seconds "
                    "(the apiserver's ?timeout= overrides per "
                    "request)")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="trivy-tpu",
        description="TPU-native security scanner")
    p.add_argument("--version", action="version",
                   version=f"trivy-tpu {__version__}")
    p.add_argument("--cache-dir",
                   default=os.path.join(
                       os.path.expanduser("~"), ".cache", "trivy-tpu"))
    p.add_argument("--quiet", "-q", action="store_true")
    p.add_argument("--debug", "-d", action="store_true")
    p.add_argument("--config", "-c", default="",
                   help="config file (default: trivy.yaml when "
                   "present); flags also bind TRIVY_* env vars")
    sub = p.add_subparsers(dest="command")

    def scan_flags(sp):
        sp.add_argument("--cache-dir",
                        default=os.path.join(
                            os.path.expanduser("~"), ".cache",
                            "trivy-tpu"))
        sp.add_argument("--severity", "-s", default=DEFAULT_SEVERITIES)
        sp.add_argument("--security-checks", default="vuln,secret")
        sp.add_argument("--vuln-type", default="os,library")
        from .report.writer import FORMATS
        sp.add_argument("--format", "-f", default="table",
                        choices=FORMATS)
        sp.add_argument("--output", "-o", default="")
        sp.add_argument("--template", "-t", default="",
                        help="output template ('@path' or inline), "
                        "used with --format template")
        sp.add_argument("--ignore-unfixed", action="store_true")
        sp.add_argument("--include-non-failures",
                        action="store_true",
                        help="include passed/excepted misconfig "
                        "checks in the results")
        sp.add_argument("--ignorefile", default=".trivyignore")
        sp.add_argument("--ignore-policy", default="",
                        help="Python policy file defining "
                        "ignore(finding) (the Rego ignore-policy "
                        "analog). WARNING: executed with full "
                        "interpreter rights, unlike the reference's "
                        "sandboxed Rego — only point it at files "
                        "you trust")
        sp.add_argument("--exit-code", type=int, default=0)
        sp.add_argument("--skip-dirs", default="")
        sp.add_argument("--skip-files", default="")
        sp.add_argument("--file-patterns", action="append",
                        default=[], metavar="TYPE:REGEX",
                        help="force files matching REGEX through the "
                        "TYPE analyzer (ref scan_flags.go:35-43), "
                        "e.g. dockerfile:Customfile; repeatable")
        sp.add_argument("--list-all-pkgs", action="store_true")
        sp.add_argument("--dependency-tree", action="store_true",
                        help="show a reversed dependency origin "
                        "tree under the vulnerability table "
                        "(table format only)")
        sp.add_argument("--backend", default="tpu",
                        choices=["tpu", "cpu", "cpu-ref"])
        sp.add_argument("--db-fixtures", default="",
                        help="comma-separated advisory fixture YAMLs")
        sp.add_argument("--compile-db", action="store_true",
                        help="flatten the advisory store into "
                        "TPU-resident tables before scanning")
        sp.add_argument("--compiled-db", default="",
                        help="load a compiled advisory DB "
                        "(path prefix from 'trivy-tpu db build')")
        sp.add_argument("--skip-db-update", action="store_true",
                        help="use the installed advisory DB even if "
                        "its metadata says it is stale "
                        "(ref --skip-db-update)")
        sp.add_argument("--secret-config", default="trivy-secret.yaml")
        sp.add_argument("--config-policy", default="",
                        help="comma-separated directories of custom "
                        "misconfig policy modules (Python files "
                        "defining POLICIES; the reference's custom-"
                        "rego analog). WARNING: executed with full "
                        "interpreter rights")
        sp.add_argument("--helm-values", default="",
                        help="comma-separated helm values files "
                        "overriding chart values.yaml")
        sp.add_argument("--helm-set", default="",
                        help="comma-separated helm key=value "
                        "overrides (--set analog)")
        sp.add_argument("--trace", action="store_true",
                        help="record misconfig evaluation traces "
                        "in the results (the rego --trace analog): "
                        "which attributes the HCL subset could not "
                        "evaluate, so 'no findings' is "
                        "distinguishable from 'couldn't evaluate'")
        sp.add_argument("--generate-default-config",
                        action="store_true",
                        help="write the resolved flag values to "
                        "trivy-default.yaml and exit (ref "
                        "run.go:354)")
        sp.add_argument("--no-cache", action="store_true")
        sp.add_argument("--cache-backend", default="fs",
                        help="layer cache backend: fs | "
                        "redis://host:port")
        sp.add_argument("--no-memo", action="store_true",
                        help="disable the findings memo "
                        "(docs/performance.md 'Findings "
                        "memoization'): every layer's detection "
                        "re-dispatches even when the same question "
                        "was answered before")
        sp.add_argument("--memo-cache", default="",
                        help="findings-memo backend override: "
                        "'memory', a directory, redis://host:port "
                        "or s3://bucket/prefix — default rides the "
                        "blob-cache tier (--cache-backend)")
        sp.add_argument("--timeout", default="5m0s",
                        help="scan timeout (e.g. 5m0s)")
        sp.add_argument("--profile-dir", default="",
                        help="older spelling of --profile-out "
                        "(--profile-out wins when both are set)")
        sp.add_argument("--profile-out", default="",
                        help="write a jax.profiler device trace + "
                        "the host profiler's collapsed stacks "
                        "(host_profile.folded) for flamegraphs "
                        "(docs/observability.md 'Host profiler')")
        sp.add_argument("--sched", default="on",
                        choices=["on", "off"],
                        help="continuous-batching scheduler for "
                        "multi-image scans (docs/serving.md); off = "
                        "the direct single-batch path")
        sp.add_argument("--sched-stats", action="store_true",
                        help="dump scheduler metrics (queue depth, "
                        "batch occupancy, host/device overlap, "
                        "latency histograms) to stderr after the "
                        "scan")
        sp.add_argument("--sched-flush-ms", type=float, default=50.0,
                        help="coalescer flush timeout in ms")
        sp.add_argument("--sched-queue", type=int, default=256,
                        help="admission queue bound (backpressure)")
        sp.add_argument("--sched-workers", type=int, default=4,
                        help="host worker pool size")
        sp.add_argument("--dispatch-depth", type=int, default=0,
                        help="device slots in flight (async "
                        "double-buffered runtime, "
                        "docs/performance.md §8): 2 uploads batch "
                        "N+1 while N computes, 1 restores the "
                        "synchronous ladder; 0 = "
                        "TRIVY_TPU_DISPATCH_DEPTH or 2")
        sp.add_argument("--coordinator", default="",
                        help="multi-host pod: host:port of process "
                        "0 (TRIVY_TPU_COORDINATOR); requires "
                        "--num-processes/--process-id")
        sp.add_argument("--num-processes", type=int, default=0,
                        help="multi-host pod: total scanner "
                        "processes (TRIVY_TPU_NUM_PROCESSES)")
        sp.add_argument("--process-id", type=int, default=-1,
                        help="multi-host pod: this process's id "
                        "(TRIVY_TPU_PROCESS_ID)")
        sp.add_argument("--tenant-config", default="",
                        help="multi-tenant QoS table "
                        "(docs/serving.md): a JSON file path or an "
                        "inline spec like "
                        "'alice:weight=4,rate=100;default:rate=50' "
                        "— per-tenant WFQ weights, max_queued/"
                        "max_inflight quotas, and token-bucket "
                        "rate/burst limits (429 + Retry-After)")
        sp.add_argument("--tenant-budget", default="",
                        help="per-tenant device-second budgets "
                        "(docs/observability.md 'Cost attribution "
                        "& goodput'): JSON file or inline "
                        "'alice:device_s=2.5,window_s=60,"
                        "action=throttle;bob:device_s=1' — a "
                        "tenant over its windowed spend is "
                        "throttled (429 + Retry-After) or "
                        "deprioritized to the budget's priority "
                        "floor")
        sp.add_argument("--fault-spec", default="",
                        help="inject deterministic faults "
                        "(docs/robustness.md): a scenario name "
                        "(cache-outage, poison-image, "
                        "device-transient, rpc-flaky, slow-host, "
                        "standard-outage, hostile-ingest ...) "
                        "optionally followed by :key=value "
                        "overrides, e.g. "
                        "poison-image:poison=img7.tar")
        sp.add_argument("--max-decompressed-bytes", type=int,
                        default=0,
                        help="ingest guard: per-target decompressed-"
                        "byte budget (default 1 GiB; "
                        "docs/robustness.md)")
        sp.add_argument("--max-files", type=int, default=0,
                        help="ingest guard: per-target archive "
                        "entry budget (default 100000)")
        sp.add_argument("--ingest-deadline-s", type=float,
                        default=0.0,
                        help="ingest guard: per-target wall-clock "
                        "deadline for image load + layer walking "
                        "(default 300s)")
        sp.add_argument("--no-ingest-guards", action="store_true",
                        help="disable the ingest resource budgets "
                        "and safe-tar checks (differential "
                        "baseline; scanning untrusted artifacts "
                        "without guards is unsafe)")
        sp.add_argument("--trace-out", default="",
                        help="write one Perfetto-loadable trace-"
                        "event JSON per request into this directory "
                        "(multi-target image scans; "
                        "docs/observability.md)")
        sp.add_argument("--log-format", default="text",
                        choices=["text", "json"],
                        help="log line format; json lines carry "
                        "trace_id/request_id so logs correlate "
                        "with traces")
        sp.add_argument("--config", "-c", default="",
                        help="config file (default: trivy.yaml)")
        sp.add_argument("--server", default="",
                        help="server URL for client/server mode "
                        "(detection runs remotely; no local DB)")
        sp.add_argument("--token", dest="auth_token", default="",
                        help="server auth token")
        sp.add_argument("--token-header", default="Trivy-Token")
        sp.add_argument("--custom-headers", default="",
                        help="comma-separated k=v headers sent to "
                        "the server")

    img = sub.add_parser("image", help="scan a container image "
                         "(tarball or OCI layout); several targets "
                         "batch-scan through the scheduler")
    img.add_argument("--input", default="",
                     help="image tarball path (docker save / OCI)")
    img.add_argument("--removed-pkgs", action="store_true",
                     help="also scan packages installed and later "
                     "removed in the Dockerfile (reconstructed "
                     "from RUN history; alpine only, needs "
                     "TRIVY_APK_INDEX_ARCHIVE_URL)")
    img.add_argument("target", nargs="*", default=[])
    scan_flags(img)

    fs = sub.add_parser("filesystem", aliases=["fs"],
                        help="scan a local directory")
    fs.add_argument("target")
    scan_flags(fs)

    rootfs = sub.add_parser("rootfs", help="scan an unpacked root "
                            "filesystem")
    rootfs.add_argument("target")
    scan_flags(rootfs)

    repo = sub.add_parser("repo", help="scan a remote or local git "
                          "repository")
    repo.add_argument("target", help="repo URL or local path")
    repo.add_argument("--branch", default="")
    repo.add_argument("--tag", default="")
    repo.add_argument("--commit", default="")
    scan_flags(repo)

    sbom = sub.add_parser("sbom", help="scan an SBOM document "
                          "(CycloneDX/SPDX, vuln checks only); "
                          "several documents, or a directory of "
                          "*.json, batch-scan through scan_boms")
    sbom.add_argument("target", nargs="+")
    scan_flags(sbom)

    cl = sub.add_parser("client", aliases=["c"],
                        help="DEPRECATED: image scan in "
                        "client/server mode (ref app.go:441 "
                        "NewClientCommand; use `image --server` "
                        "instead)")
    cl.add_argument("--remote", default="http://localhost:4954",
                    help="server address (the deprecated spelling "
                    "of --server)")
    cl.add_argument("--input", default="")
    cl.add_argument("target", nargs="?", default="")
    scan_flags(cl)

    conf = sub.add_parser("config", aliases=["conf"],
                          help="scan config files for "
                          "misconfigurations only (ref "
                          "app.go:533 NewConfigCommand)")
    conf.add_argument("target")
    scan_flags(conf)

    k8s = sub.add_parser(
        "k8s", help="scan kubernetes manifests/cluster state "
        "(misconfigs on workloads; image vulns via --images-dir)")
    k8s.add_argument("target",
                     help="manifest file or directory of exported "
                     "cluster manifests")
    k8s.add_argument("--report", default="summary",
                     choices=["summary", "all"])
    k8s.add_argument("--images-dir", default="",
                     help="directory of image tarballs named "
                     "<ref with /:@ as _>.tar")
    k8s.add_argument("--compliance", default="",
                     help="compliance spec: built-in name (nsa) or "
                     "a YAML spec file")
    scan_flags(k8s)

    watch = sub.add_parser(
        "watch", help="continuous scanning: subscribe to registry "
        "push events (Docker Registry v2 notification webhooks, or "
        "a seeded synthetic source) and keep the fleet scanned "
        "(docs/serving.md 'Continuous scanning & admission "
        "control')")
    watch.add_argument("target", nargs="*", default=[],
                       help="image tarballs the synthetic source "
                       "draws push events from (webhook sources "
                       "resolve refs via --images-dir instead)")
    watch.add_argument("--watch-source", default="webhook",
                       help="event source: 'webhook' (serve "
                       "POST /registry/notifications on --listen) "
                       "or 'synthetic[:rate=5,n=64,seed=7]' "
                       "(seeded Poisson replay over the targets)")
    watch.add_argument("--listen", default="127.0.0.1:4956",
                       help="host:port for the HTTP plane "
                       "(notification webhook, /metrics, "
                       "/k8s/admission); synthetic runs skip it "
                       "with --listen ''")
    watch.add_argument("--images-dir", default="",
                       help="resolve pushed image refs to local "
                       "tarballs named <ref with /:@ as _>.tar "
                       "(the k8s --images-dir contract)")
    watch.add_argument("--debounce-ms", type=float, default=250.0,
                       help="per-digest debounce window: a tag "
                       "repushed in a burst scans once")
    watch.add_argument("--max-inflight", type=int, default=32,
                       help="in-flight watermark: stop pulling the "
                       "event source at this many outstanding scans")
    watch.add_argument("--checkpoint", default="",
                       help="cursor checkpoint file: a restarted "
                       "watch resumes after the last resolved event "
                       "instead of re-scanning the backlog")
    watch.add_argument("--watch-tenant", default="watch",
                       help="tenant identity watch submissions "
                       "carry (QoS/SLO scoping, docs/serving.md)")
    watch.add_argument("--watch-priority", type=int, default=0)
    watch.add_argument("--max-events", type=int, default=0,
                       help="stop after this many events "
                       "(0 = run until SIGINT / source exhausted)")
    _admission_flags(watch)
    scan_flags(watch)

    aws = sub.add_parser(
        "aws", help="scan AWS account state (exported account-state "
        "JSON; live API walk is a seam)")
    aws.add_argument("--account-state", required=True,
                     help="exported account state JSON (the "
                     "account-state cache shape)")
    aws.add_argument("--service", default="",
                     help="comma-separated service filter "
                     "(s3,ec2,iam,cloudtrail)")
    scan_flags(aws)

    db = sub.add_parser("db", help="advisory DB operations")
    dbsub = db.add_subparsers(dest="db_command")
    build = dbsub.add_parser(
        "build", help="compile fixtures into persistent TPU-resident "
        "advisory tables")
    build.add_argument("--from-fixtures", default="",
                       help="comma-separated advisory fixture YAMLs")
    build.add_argument("--from-boltdb", default="",
                       help="trivy-db BoltDB file (the reference's "
                       "native advisory store format)")
    build.add_argument("--output", "-o", required=True,
                       help="output path prefix (.npz)")
    upd = dbsub.add_parser(
        "update", help="install an advisory DB distribution into "
        "the cache dir (ref pkg/db/db.go Download)")
    upd.add_argument("--from-oci-layout", default="", required=True,
                     help="local OCI image layout dir holding the "
                     "trivy-db layer (what a registry pull yields; "
                     "the network transport is an environment seam)")
    upd.add_argument("--cache-dir",
                     default=os.path.join(
                         os.path.expanduser("~"), ".cache",
                         "trivy-tpu"))
    upd.add_argument("--compile", action="store_true",
                     help="also compile the installed DB into "
                     "TPU-resident tables at <cache>/db/compiled")

    srv = sub.add_parser("server", help="run in server mode "
                         "(owns cache + advisory DB + TPU dispatch)")
    srv.add_argument("--listen", default="127.0.0.1:4954")
    srv.add_argument("--token", dest="auth_token", default="")
    srv.add_argument("--token-header", default="Trivy-Token")
    srv.add_argument("--cache-dir",
                     default=os.path.join(
                         os.path.expanduser("~"), ".cache",
                         "trivy-tpu"))
    srv.add_argument("--db-fixtures", default="")
    srv.add_argument("--compiled-db", default="",
                     help="compiled advisory DB path prefix; the "
                     "server hot-swaps when the file changes")
    srv.add_argument("--db-watch-interval", type=float, default=60.0)
    srv.add_argument("--no-memo", action="store_true",
                     help="disable the findings memo "
                     "(docs/performance.md)")
    srv.add_argument("--memo-cache", default="",
                     help="findings-memo backend override "
                     "('memory', a directory, redis:// or s3://); "
                     "default persists under --cache-dir")
    srv.add_argument("--impact-index", action="store_true",
                     help="maintain the inverted (package, CVE) -> "
                     "layers -> images findings index as a write-"
                     "through side effect of the memo tier, rebuild "
                     "it from the shared tier on boot, and serve "
                     "GET /impact?cve= (docs/serving.md 'CVE impact "
                     "queries & push re-scans'); requires the memo")
    srv.add_argument("--sched", default="on",
                     choices=["on", "off"],
                     help="coalesce concurrent Scan RPCs through "
                     "the continuous-batching scheduler; metrics "
                     "at GET /metrics (docs/serving.md)")
    srv.add_argument("--sched-flush-ms", type=float, default=50.0)
    srv.add_argument("--sched-queue", type=int, default=256)
    srv.add_argument("--sched-workers", type=int, default=4)
    srv.add_argument("--dispatch-depth", type=int, default=0,
                     help="device slots in flight "
                     "(docs/performance.md §8); 0 = "
                     "TRIVY_TPU_DISPATCH_DEPTH or 2")
    srv.add_argument("--coordinator", default="",
                     help="multi-host pod: host:port of process 0 "
                     "(TRIVY_TPU_COORDINATOR)")
    srv.add_argument("--num-processes", type=int, default=0,
                     help="multi-host pod: total scanner processes")
    srv.add_argument("--process-id", type=int, default=-1,
                     help="multi-host pod: this process's id")
    srv.add_argument("--tenant-config", default="",
                     help="multi-tenant QoS table (docs/serving.md "
                     "'Multi-tenant QoS'): JSON file or inline "
                     "'name:weight=4,rate=100;...' — tenants come "
                     "from the Trivy-Tenant header or body field; "
                     "over-quota tenants get 429 + Retry-After "
                     "while compliant tenants' p99 holds")
    srv.add_argument("--tenant-budget", default="",
                     help="per-tenant device-second budgets "
                     "(docs/observability.md 'Cost attribution & "
                     "goodput'): JSON file or inline "
                     "'alice:device_s=2.5,window_s=60,"
                     "action=throttle;bob:device_s=1,"
                     "action=deprioritize' — admission reads the "
                     "tenant's windowed spend from the cost ledger "
                     "(GET /costs); over budget means 429 + "
                     "Retry-After (throttle) or a priority-floor "
                     "clamp inside the tenant's own WFQ lane "
                     "(deprioritize)")
    srv.add_argument("--sched-deadline", default="",
                     help="default per-request deadline "
                     "(Go duration, e.g. 30s; requests may "
                     "override via body deadline_s)")
    srv.add_argument("--fault-spec", default="",
                     help="inject deterministic faults into the "
                     "server (docs/robustness.md)")
    srv.add_argument("--drain-timeout", type=float, default=30.0,
                     help="SIGTERM graceful-drain bound in seconds "
                     "(in-flight scans finish, new work gets 503)")
    srv.add_argument("--trace-out", default="",
                     help="export every completed request trace as "
                     "Perfetto-loadable JSON into this directory "
                     "(traces are also served at GET /trace/<id>)")
    srv.add_argument("--log-format", default="text",
                     choices=["text", "json"],
                     help="log line format; json lines carry "
                     "trace_id/request_id (docs/observability.md)")
    srv.add_argument("--slo-config", default="",
                     help="service-level objectives "
                     "(docs/observability.md 'SLOs & burn rates'): "
                     "inline 'name:kind=availability,"
                     "objective=0.999;lat:kind=latency,"
                     "objective=0.95,threshold_s=2.5' — burn-rate "
                     "verdicts at GET /slo, gauges on /metrics; "
                     "default: 99%% availability + 95%% under 30s")
    srv.add_argument("--federate-peers", default="",
                     help="metrics/SLO federation "
                     "(docs/observability.md 'Fleet plane'): "
                     "'name=http://host:port,...' (or bare URLs); "
                     "this replica then serves the merged fleet "
                     "exposition at GET /metrics/federate and fleet "
                     "burn-rate verdicts under GET /slo 'fleet'")
    srv.add_argument("--federate-timeout", type=float, default=2.0,
                     help="per-peer snapshot-pull timeout in "
                     "seconds; a slow peer is marked stale, never "
                     "awaited past this")
    srv.add_argument("--federate-stale-after", type=float,
                     default=60.0,
                     help="seconds after which a peer's last-good "
                     "snapshot stops counting as fresh (the peer is "
                     "exported with trivy_tpu_federate_peer_stale=1)")
    srv.add_argument("--replica-name", default="",
                     help="this replica's value for the federated "
                     "'replica' metrics label (default: the "
                     "--listen address)")
    _admission_flags(srv)
    srv.add_argument("--images-dir", default="",
                     help="resolve admission-webhook image refs to "
                     "local tarballs named <ref with /:@ as _>.tar; "
                     "without it admission misses apply the fail "
                     "stance")
    srv.add_argument("--compile-cache", default="",
                     help="AOT shape precompile at boot into this "
                     "persistent compilation cache directory "
                     "(docs/serving.md 'Elastic lifecycle'): the "
                     "bucket-ladder interval and DFA kernel shapes "
                     "compile before /healthz goes ready, and a "
                     "later boot of the same (jax version, backend, "
                     "rule set) deserializes instead of rebuilding")
    srv.add_argument("--prewarm-members", default="",
                     help="comma-separated names of the replicas "
                     "already on the routing ring: before /healthz "
                     "reports ready this replica computes its post-"
                     "join key ranges, walks the shared memo tier "
                     "for them, and stages resident tables "
                     "(docs/serving.md 'Elastic lifecycle'); "
                     "requires the memo")
    srv.add_argument("--prewarm-deadline", type=float, default=5.0,
                     help="prewarm walk bound in seconds — past it "
                     "the replica joins cold instead of wedging the "
                     "scale-up")
    srv.add_argument("--profile-out", default="",
                     help="opt-in device trace: jax.profiler trace "
                     "into this directory plus the host profiler's "
                     "collapsed stacks (host_profile.folded), "
                     "capturing the server's first "
                     "TRIVY_TPU_PROFILE_SECONDS (default 60) so a "
                     "long-lived server neither buffers an "
                     "unbounded trace nor defers the artifact to "
                     "shutdown; the always-on host profiler is "
                     "also served at GET /debug/profile?seconds=N")

    rt = sub.add_parser("route", help="run the scan-router front: "
                        "consistent-hash sharding over N server "
                        "replicas with zero-loss failover and "
                        "SLO-driven autoscaling (docs/serving.md)")
    rt.add_argument("--listen", default="127.0.0.1:4955")
    rt.add_argument("--replicas", default="",
                    help="backend replicas, "
                    "'name=http://host:port,...' (or bare URLs) — "
                    "same syntax as --federate-peers; may be empty "
                    "when --scaler brings the fleet up")
    rt.add_argument("--token", dest="auth_token", default="",
                    help="shared fleet token: required from "
                    "clients AND presented to replicas")
    rt.add_argument("--token-header", default="Trivy-Token")
    rt.add_argument("--vnodes", type=int, default=64,
                    help="virtual nodes per replica on the hash "
                    "ring")
    rt.add_argument("--capacity-factor", type=float, default=1.25,
                    help="bounded-load cap: a replica takes at "
                    "most ceil(cf * (inflight+1) / n) requests "
                    "before the hot digest spills to the next "
                    "ring owner")
    rt.add_argument("--probe-interval", type=float, default=1.0,
                    help="seconds between /healthz probes of each "
                    "replica (drain visibility, breaker recovery)")
    rt.add_argument("--upstream-timeout", type=float, default=300.0,
                    help="per-forward upstream timeout in seconds; "
                    "a timed-out replica is failed over with the "
                    "same idempotency key")
    rt.add_argument("--scaler", default="off",
                    choices=["off", "sim", "subprocess"],
                    help="SLO-driven autoscaler: 'subprocess' "
                    "spawns sim replicas as child processes "
                    "(tests/demo); production wires its own "
                    "ReplicaController")
    rt.add_argument("--scaler-min", type=int, default=1)
    rt.add_argument("--scaler-max", type=int, default=8)
    rt.add_argument("--scaler-interval", type=float, default=2.0)
    rt.add_argument("--fault-spec", default="",
                    help="inject deterministic router faults "
                    "(replica-flaky response drops; "
                    "docs/robustness.md)")

    soak = sub.add_parser(
        "soak", help="run a registry-scale soak scenario against a "
        "routed CPU-sim fleet: seeded synthetic registry, scripted "
        "chaos on a compressed clock, fleet SLO + books + leak "
        "verdicts (docs/robustness.md 'Soak & chaos testing')")
    soak.add_argument("--scenario", default="soak-smoke",
                      help="preset name (soak, soak-smoke) or a "
                      "JSON ScenarioSpec file")
    soak.add_argument("--replicas", type=int, default=3)
    soak.add_argument("--seed", type=int, default=0,
                      help="override the scenario seed (0 = keep)")
    soak.add_argument("--duration", type=float, default=0.0,
                      help="override virtual duration seconds")
    soak.add_argument("--compression", type=float, default=0.0,
                      help="override virtual-seconds-per-real-"
                      "second")
    soak.add_argument("--mode", default="inproc",
                      choices=["inproc", "subprocess"],
                      help="replica isolation: in-process sims or "
                      "one OS process each")
    soak.add_argument("--report", default="",
                      help="write the full JSON report here "
                      "(sort_keys; same-seed runs diff cleanly)")
    soak.add_argument("--epoch", type=float, default=0.5,
                      help="audit/verdict sampling period, real "
                      "seconds")
    soak.add_argument("--service-ms", type=float, default=3.0)

    plug = sub.add_parser("plugin", help="manage plugins")
    plugsub = plug.add_subparsers(dest="plugin_command")
    pi = plugsub.add_parser("install", help="install from a local "
                            "directory or archive")
    pi.add_argument("source")
    pu = plugsub.add_parser("uninstall")
    pu.add_argument("name")
    plugsub.add_parser("list")
    pinfo = plugsub.add_parser("info")
    pinfo.add_argument("name")
    prun = plugsub.add_parser("run")
    prun.add_argument("name")
    prun.add_argument("plugin_args", nargs=argparse.REMAINDER)

    mod = sub.add_parser("module", aliases=["m"],
                         help="manage extension modules (ref "
                         "app.go:693 NewModuleCommand)")
    modsub = mod.add_subparsers(dest="module_command")
    mi = modsub.add_parser("install", aliases=["i"],
                           help="install a module from a local "
                           ".py file or a directory of them (the "
                           "reference pulls from an OCI repo; the "
                           "registry fetch is the egress seam)")
    mi.add_argument("source")
    mu = modsub.add_parser("uninstall", aliases=["u"])
    mu.add_argument("name")
    modsub.add_parser("list")

    imp = sub.add_parser(
        "impact", help="ask a replica server or the router fleet "
        "front which layers/images a CVE affects "
        "(GET /impact?cve=, docs/serving.md 'CVE impact queries & "
        "push re-scans')")
    imp.add_argument("--server", required=True,
                     help="server or router base URL")
    imp.add_argument("--cve", required=True)
    imp.add_argument("--token", dest="auth_token", default="")
    imp.add_argument("--token-header", default="Trivy-Token")
    imp.add_argument("--timeout", type=float, default=5.0)

    sub.add_parser("version", help="print version")
    return p


_KNOWN_COMMANDS = ("image", "filesystem", "fs", "rootfs", "repo",
                   "sbom", "k8s", "aws", "db", "server", "route",
                   "watch", "plugin", "config", "conf", "module",
                   "m", "client", "c", "impact", "soak",
                   "version")


def main(argv=None) -> int:
    from .flag import (ScanTimeout, apply_external_defaults,
                       parse_duration, scan_deadline)
    # application-level filter: the donated kernels trigger XLA's
    # "not usable" aliasing advisory on every compile (bool/uint16
    # outputs can never alias their int32/uint8 payload inputs —
    # expected, see ops/intervals.py); silence it for CLI runs only,
    # never in the library, so embedders keep the signal
    import warnings as _warnings
    _warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")
    raw_argv = list(sys.argv[1:] if argv is None else argv)
    # unknown subcommands dispatch to installed plugins (app.go:96)
    if raw_argv and not raw_argv[0].startswith("-") and \
            raw_argv[0] not in _KNOWN_COMMANDS:
        from .plugin import run_with_args
        code = run_with_args(raw_argv[0], raw_argv[1:])
        if code is not None:
            return code
    parser = build_parser()
    if not raw_argv or raw_argv[0] != "plugin":
        # plugin argv (incl. REMAINDER passthrough) is never
        # inspected for --config or rewritten by env defaults
        apply_external_defaults(parser, raw_argv)
    args = parser.parse_args(argv)
    from .utils.log import set_format
    set_format(getattr(args, "log_format", "") or "text")
    timeout_s = 0.0
    if getattr(args, "timeout", ""):
        try:
            timeout_s = parse_duration(args.timeout)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    from .artifact.redis_cache import RedisError
    from .artifact.s3_cache import S3Error
    from .ops.program import DeviceProgramError
    from .runtime.device import DeviceUnavailable
    # --profile-out supersedes --profile-dir (same jax trace, plus
    # the host profiler's folded stacks); one wrapper, never two
    # stacked jax.profiler.trace contexts
    profile_dir = getattr(args, "profile_out", "") or \
        getattr(args, "profile_dir", "")
    # a one-shot scan traces end-to-end; the SERVER would hold the
    # jax trace open (and buffering) for its whole lifetime and
    # write nothing until shutdown — bound its capture window so the
    # flag yields an artifact while the server is still up
    profile_window = float(
        os.environ.get("TRIVY_TPU_PROFILE_SECONDS", "60")) \
        if args.command in ("server", "watch") else 0.0
    try:
        with scan_deadline(timeout_s), \
                _profiled(profile_dir, profile_window,
                          device=_owns_device(args)):
            return _dispatch(args)
    except (RedisError, S3Error, ValueError) as e:
        # cache-backend connect/IO failures and bad backend values
        # fail cleanly, never with a traceback
        print(f"error: {e}", file=sys.stderr)
        return 1
    except (DeviceUnavailable, DeviceProgramError) as e:
        # no device where one was asked for, or a kernel that does
        # not compile: never a fallback, never exit 0
        print(f"error: {e}", file=sys.stderr)
        return 1
    except ScanTimeout:
        print(f"error: scan timeout of {args.timeout} exceeded "
              "(raise with --timeout)", file=sys.stderr)
        return 1


import contextlib


@contextlib.contextmanager
def _profiled(profile_dir: str, max_seconds: float = 0.0,
              device: bool = True):
    """--profile-out / --profile-dir: capture a jax.profiler device
    trace of the scan (the reference's pprof/trace analog; SURVEY §5
    tracing row) plus the host profiler's collapsed stacks
    (host_profile.folded). The trace opens in TensorBoard/Perfetto;
    phase-level host/device timings live in
    BatchScanRunner.last_stats and the metrics snapshots. The single
    jax-trace wrapper lives in obs.profiler.device_trace; a process
    that owns no device (``device=False``: thin clients, cpu-ref)
    writes the host profile only."""
    if not profile_dir:
        yield
        return
    from .obs.profiler import device_trace
    try:
        with device_trace(profile_dir, max_seconds=max_seconds,
                          device=device):
            yield
    finally:
        # the trace flushes even when the scan errors or times out —
        # exactly when it is most wanted
        print(f"profile trace written to {profile_dir}",
              file=sys.stderr)


# commands whose scans dispatch kernels (vuln → interval match,
# secret → DFA sieve); everything else is host-only
_SCAN_COMMANDS = ("image", "filesystem", "fs", "rootfs", "repo",
                  "sbom", "k8s", "client", "c", "watch")


def _device_backend(args) -> str:
    """The backend this process resolves a device for: the
    ``--backend`` flag, except that a process which dispatches no
    kernel resolves none (``cpu-ref``) — a ``--server`` client (the
    server owns the chip; the client-side secret pass runs the host
    engine, byte-identical by construction), the server-less
    commands, and scans with neither vuln nor secret checks."""
    if args.command == "server":
        return "tpu"
    if args.command not in _SCAN_COMMANDS:
        return "cpu-ref"
    if getattr(args, "server", "") or (
            args.command in ("client", "c")
            and getattr(args, "remote", "")):
        return "cpu-ref"
    checks = {c for c in getattr(args, "security_checks",
                                 "").split(",") if c}
    if args.command != "sbom" and not checks & {"vuln", "secret"}:
        return "cpu-ref"
    if args.command == "k8s" and not getattr(args, "images_dir", ""):
        return "cpu-ref"        # manifests only: no image to scan
    return getattr(args, "backend", "tpu")


def _owns_device(args) -> bool:
    return _device_backend(args) != "cpu-ref"


def _resolve_device(args):
    """Place the compile cache and check the device, first thing in
    every command that dispatches kernels (after the multi-host
    join where there is one, so ``jax.devices()`` is the global
    set). Raises DeviceUnavailable — main() turns it into exit 1."""
    from .runtime.device import resolve_device
    return resolve_device(_device_backend(args))


def _dispatch(args) -> int:
    if args.command in (None, "version"):
        print(f"trivy-tpu {__version__}")
        return 0
    if getattr(args, "generate_default_config", False):
        return _generate_default_config(args)
    if args.command in ("image", "filesystem", "fs", "rootfs",
                        "repo", "sbom", "k8s", "config", "conf",
                        "client", "c"):
        from .module import Manager as _ModuleManager
        _ModuleManager().load()
    if args.command in ("image",):
        return run_image(args)
    if args.command in ("filesystem", "fs", "rootfs"):
        return run_fs(args)
    if args.command in ("config", "conf"):
        # misconfiguration-only entry point: the fs pipeline with
        # the scanners pinned to config (ref app.go:533)
        args.security_checks = "config"
        args.vuln_type = ""
        return run_fs(args)
    if args.command in ("client", "c"):
        # deprecated alias for `image --server` (app.go:441-447:
        # --remote replaces --server)
        print("WARN: 'client' is deprecated; use "
              "'image --server' instead", file=sys.stderr)
        # an explicit --server wins over the deprecated --remote
        args.server = args.server or args.remote
        return run_image(args)
    if args.command in ("module", "m"):
        return run_module(args)
    if args.command == "repo":
        return run_repo(args)
    if args.command == "sbom":
        return run_sbom(args)
    if args.command == "db":
        return run_db(args)
    if args.command == "server":
        return run_server(args)
    if args.command == "route":
        return run_route(args)
    if args.command == "watch":
        return run_watch(args)
    if args.command == "k8s":
        return run_k8s(args)
    if args.command == "plugin":
        return run_plugin(args)
    if args.command == "aws":
        return run_aws(args)
    if args.command == "impact":
        return run_impact(args)
    if args.command == "soak":
        return run_soak_cmd(args)
    return 2


def run_soak_cmd(args) -> int:
    """``trivy-tpu soak --scenario NAME|FILE``: one scenario, one
    fleet, one verdict. Exit 0 iff books balance, designed trips
    trip exactly, and the leak audit passes."""
    from .soak import load_scenario, run_soak
    scenario = load_scenario(args.scenario, seed=args.seed,
                             duration_s=args.duration,
                             compression=args.compression)
    report = run_soak(scenario, replicas=args.replicas,
                      mode=args.mode, report_path=args.report,
                      epoch_s=args.epoch,
                      service_ms=args.service_ms)
    stable = report["stable"]
    trip = report["slo"]["trip"]
    print(f"scenario {stable['scenario']} seed {stable['seed']} "
          f"({stable['arrivals']} arrivals, "
          f"{stable['steps']} steps, "
          f"{report['wall']['duration_s']}s wall)")
    print(f"  books: lost={stable['lost']} "
          f"balanced={stable['books_balanced']}")
    print(f"  slo:   trips_exact={stable['trips_exact']} "
          f"dumps={trip['dumps']}")
    print(f"  leak:  audit_ok={stable['audit_ok']}")
    sustained = report["throughput"]["sustained"]
    if sustained["seconds"]:
        print(f"  ips:   {sustained['ips']} sustained over "
              f"{sustained['seconds']}s steady state")
    if args.report:
        print(f"  report: {args.report}")
    ok = (stable["books_balanced"] and stable["trips_exact"]
          and stable["audit_ok"])
    return 0 if ok else 1


def run_impact(args) -> int:
    """``trivy-tpu impact --server URL --cve ID``: one HTTP query
    against a replica's slice or the router front's federated
    union. A partial answer (``complete: false``) still exits 0 —
    Federator semantics; the flag is the caller's signal."""
    import urllib.error
    from .impact.federate import fetch_impact
    try:
        out = fetch_impact(args.server, args.cve,
                           token=args.auth_token,
                           token_header=args.token_header,
                           timeout_s=args.timeout)
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"error: impact query: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out, indent=2, sort_keys=True))
    return 0


def run_aws(args) -> int:
    """ref pkg/cloud/aws/commands/run.go over cached account state."""
    from .cloud import load_account_state, scan_account
    if _reject_unwired_fault_spec(args):
        return 2
    try:
        state = load_account_state(args.account_state)
    except (OSError, ValueError) as e:
        print(f"error: account state: {e}", file=sys.stderr)
        return 1
    from .cloud import KNOWN_SERVICES
    services = [s.strip().lower()
                for s in args.service.split(",") if s.strip()]
    unknown = [s for s in services if s not in KNOWN_SERVICES]
    if unknown:
        print(f"error: unknown service(s) {', '.join(unknown)}; "
              f"choose from {', '.join(KNOWN_SERVICES)}",
              file=sys.stderr)
        return 2
    results = scan_account(state, services or None)
    report = Report(
        artifact_name=args.account_state,
        artifact_type="aws_account",
        metadata=Metadata(),
        results=results,
    )
    return _finish(args, report)


def _generate_default_config(args) -> int:
    """--generate-default-config: dump the resolved flag values
    (CLI > env > config-file layering already applied) to
    trivy-default.yaml, refusing to overwrite — viper's
    SafeWriteConfigAs (ref run.go:354). Keys are the FLAG names
    (--token → ``token``), exactly what apply_external_defaults
    reads back, so the file round-trips through --config."""
    import yaml
    from .flag import _walk_parsers
    dest_to_flag = {}
    for p in _walk_parsers(build_parser()):
        for action in p._actions:
            longs = [o for o in action.option_strings
                     if o.startswith("--")]
            if longs:
                dest_to_flag.setdefault(action.dest, longs[0][2:])
    skip = {"command", "target", "input", "generate_default_config",
            "help", "version", "config"}
    doc = {}
    for key, value in vars(args).items():
        flag = dest_to_flag.get(key)
        if flag is None or key in skip:
            continue
        doc[flag] = value
    out = "trivy-default.yaml"
    try:
        with open(out, "x", encoding="utf-8") as f:
            yaml.safe_dump(doc, f, sort_keys=True,
                           default_flow_style=False)
    except FileExistsError:
        print(f"error: {out} already exists", file=sys.stderr)
        return 1
    print(f"wrote {out}")
    return 0


def run_module(args) -> int:
    """module install/uninstall/list (ref app.go:693)."""
    from . import module as module_mod
    cmd = args.module_command
    if cmd in ("install", "i"):
        try:
            names = module_mod.install(args.source)
        except (ValueError, OSError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        for name in names:
            print(f"installed module {name}")
        return 0
    if cmd in ("uninstall", "u"):
        if not module_mod.uninstall(args.name):
            print(f"error: no such module: {args.name}",
                  file=sys.stderr)
            return 1
        print(f"uninstalled module {args.name}")
        return 0
    if cmd == "list":
        for stem, name, version in module_mod.list_installed():
            print(f"{stem}\t{name}\t{version}")
        return 0
    print("usage: trivy-tpu module {install,uninstall,list}",
          file=sys.stderr)
    return 2


def run_plugin(args) -> int:
    from . import plugin as plugin_mod
    cmd = args.plugin_command
    if cmd == "install":
        try:
            p = plugin_mod.install(args.source)
        except (ValueError, OSError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        print(f"installed plugin {p.name} {p.version}")
        return 0
    if cmd == "uninstall":
        if not plugin_mod.uninstall(args.name):
            print(f"error: no such plugin: {args.name}",
                  file=sys.stderr)
            return 1
        print(f"uninstalled plugin {args.name}")
        return 0
    if cmd == "list":
        for p in plugin_mod.load_all():
            print(f"{p.name}\t{p.version}\t{p.usage or p.description}")
        return 0
    if cmd == "info":
        p = plugin_mod.load(args.name)
        if p is None:
            print(f"error: no such plugin: {args.name}",
                  file=sys.stderr)
            return 1
        print(f"name: {p.name}\nversion: {p.version}\n"
              f"usage: {p.usage}\ndescription: {p.description}")
        return 0
    if cmd == "run":
        code = plugin_mod.run_with_args(args.name, args.plugin_args)
        if code is None:
            print(f"error: no such plugin: {args.name}",
                  file=sys.stderr)
            return 1
        return code
    print("error: unknown plugin subcommand", file=sys.stderr)
    return 2


def run_k8s(args) -> int:
    """ref pkg/k8s/commands/run.go:58-151 — enumerate, scan, render."""
    from .k8s import K8sScanner, ManifestClient, build_runner
    from .k8s.report import k8s_failed, write_k8s_report
    if _reject_unwired_fault_spec(args):
        return 2
    if not os.path.exists(args.target):
        print(f"error: no such path: {args.target}", file=sys.stderr)
        return 1
    if args.compliance and args.format not in ("table", "json"):
        print(f"error: compliance reports support table/json, not "
              f"{args.format}", file=sys.stderr)
        return 2
    _resolve_device(args)
    checks = [c for c in args.security_checks.split(",") if c]
    store = _store(args)
    runner = None
    if args.images_dir and {"vuln", "secret"} & set(checks):
        # the fleet path, on a mesh over every visible chip where
        # there is more than one (k8s.build_runner)
        try:
            sched = _sched_config(args) if args.sched == "on" \
                else "off"
        except ValueError as e:
            print(f"error: --tenant-config/--tenant-budget: {e}",
                  file=sys.stderr)
            return 2
        runner = build_runner(store, backend=args.backend,
                              sched=sched)
    scanner = K8sScanner(
        store=store,
        backend=args.backend,
        images_dir=args.images_dir,
        security_checks=checks,
        runner=runner)
    try:
        report = scanner.scan(ManifestClient(args.target))
    finally:
        if runner is not None:
            runner.close()
    import copy
    compliance_results = [copy.deepcopy(res) for group in
                          (report.misconfigurations,
                           report.vulnerabilities)
                          for r in group for res in r.results] \
        if args.compliance else []
    from .scan.filter import IgnorePolicyError, load_ignore_policy
    try:
        policy = load_ignore_policy(
            getattr(args, "ignore_policy", ""))
        for res in report.vulnerabilities + \
                report.misconfigurations:
            filter_results(
                res.results, _severities(args.severity),
                ignore_unfixed=args.ignore_unfixed,
                ignored_ids=load_ignore_file(args.ignorefile),
                policy=policy,
                include_non_failures=getattr(
                    args, "include_non_failures", False))
    except (OSError, IgnorePolicyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    out = open(args.output, "w") if args.output else sys.stdout
    try:
        if args.compliance:
            # compliance maps the RAW scan outcome — severity and
            # non-failure filtering must not blank out controls
            from .compliance import (build_report, load_spec,
                                     write_compliance)
            try:
                spec = load_spec(args.compliance)
            except (OSError, ValueError) as e:
                print(f"error: compliance spec: {e}",
                      file=sys.stderr)
                return 1
            write_compliance(
                build_report(spec, compliance_results),
                fmt=args.format, output=out)
        else:
            write_k8s_report(report, fmt=args.format,
                             mode=args.report, output=out)
    finally:
        if args.output:
            out.close()
    if args.exit_code and k8s_failed(report):
        return args.exit_code
    return 0


def run_server(args) -> int:
    from .rpc.server import build_server, serve_forever
    host, _, port = args.listen.rpartition(":")
    if not port.isdigit():
        print(f"error: --listen needs host:port, got "
              f"{args.listen!r}", file=sys.stderr)
        return 2
    try:
        store = _store(args)
    except (OSError, ValueError) as e:
        # a missing compiled DB is fine — the watch worker swaps it
        # in when `db build` produces it
        if args.compiled_db:
            print(f"advisory db not loadable yet ({e}); waiting for "
                  f"{args.compiled_db}.npz", file=sys.stderr)
            store = AdvisoryStore()
        else:
            print(f"error: {e}", file=sys.stderr)
            return 1
    _trace_out(args)
    slos = None
    if getattr(args, "slo_config", ""):
        from .obs.slo import parse_slo_config
        try:
            slos = parse_slo_config(args.slo_config)
        except ValueError as e:
            print(f"error: --slo-config: {e}", file=sys.stderr)
            return 2
    rc = _init_multihost(args)
    if rc:
        return rc
    _resolve_device(args)
    sched = "off"
    if getattr(args, "sched", "on") == "on":
        try:
            sched = _sched_config(args)
        except ValueError as e:
            print(f"error: --tenant-config/--tenant-budget: "
                  f"{e}", file=sys.stderr)
            return 2
        if getattr(args, "sched_deadline", ""):
            from .flag import parse_duration
            try:
                sched.default_deadline_s = parse_duration(
                    args.sched_deadline)
            except ValueError as e:
                print(f"error: --sched-deadline: {e}",
                      file=sys.stderr)
                return 2
    federator = None
    if getattr(args, "federate_peers", ""):
        from .obs.federate import Federator, parse_peers
        try:
            peers = parse_peers(args.federate_peers)
        except ValueError as e:
            print(f"error: --federate-peers: {e}", file=sys.stderr)
            return 2
        federator = Federator(
            peers, token=args.auth_token,
            token_header=args.token_header,
            timeout_s=getattr(args, "federate_timeout", 2.0),
            stale_after_s=getattr(args, "federate_stale_after",
                                  60.0))
    no_memo = getattr(args, "no_memo", False)
    prewarm_members = [m.strip() for m in
                       getattr(args, "prewarm_members",
                               "").split(",") if m.strip()]
    for flag, given in (("--impact-index",
                         getattr(args, "impact_index", False)),
                        ("--prewarm-members", prewarm_members)):
        if given and no_memo:
            print(f"error: {flag} needs the findings memo "
                  "(drop --no-memo)", file=sys.stderr)
            return 2
    # scheduler, memo, cache, server and the warm of the interval
    # ladder: one function, which the benchmark's served cell calls
    # too (rpc/server.build_server)
    server = build_server(
        store=store, sched=sched, slos=slos,
        cache_dir=args.cache_dir, memo=not no_memo,
        memo_uri=getattr(args, "memo_cache", ""),
        fault_injector=_fault_injector(args),
        impact_index=getattr(args, "impact_index", False),
        compile_cache_dir=getattr(args, "compile_cache", ""),
        replica_name=(getattr(args, "replica_name", "")
                      or args.listen),
        token=args.auth_token, token_header=args.token_header,
        federator=federator, prewarm_members=prewarm_members,
        prewarm_deadline_s=getattr(args, "prewarm_deadline", 5.0))
    adm_runner = None
    try:
        server.admission, adm_runner = _admission_controller(
            args, server)
    except ValueError as e:
        server.close()
        print(f"error: --admission-policy: {e}", file=sys.stderr)
        return 2
    print(f"trivy-tpu server listening on {args.listen}")
    try:
        # its shutdown closes the scheduler build_server made
        return serve_forever(
            host or "127.0.0.1", int(port), server,
            db_watch_prefix=args.compiled_db,
            db_watch_interval_s=args.db_watch_interval,
            drain_timeout_s=getattr(args, "drain_timeout", 30.0))
    finally:
        if adm_runner is not None:
            adm_runner.close()


def run_route(args) -> int:
    """``trivy-tpu route``: the fleet front (docs/serving.md "Scan
    router & autoscaling") — consistent-hash sharding by layer
    digest across the --replicas set, /healthz probing, breaker
    ejection, zero-loss failover, optional SLO-driven autoscaling."""
    from .obs.federate import parse_peers
    from .router import (Autoscaler, HealthProber, RouterServer,
                         ScalerPolicy, ScanRouter,
                         SimReplicaController,
                         SubprocessReplicaController, serve_router)
    from .router.scaler import federated_verdicts

    host, _, port = args.listen.rpartition(":")
    if not port.isdigit():
        print(f"error: --listen needs host:port, got "
              f"{args.listen!r}", file=sys.stderr)
        return 2
    try:
        replicas = parse_peers(args.replicas) \
            if args.replicas else []
    except ValueError as e:
        print(f"error: --replicas: {e}", file=sys.stderr)
        return 2
    if not replicas and args.scaler == "off":
        print("error: --replicas is empty and --scaler off: "
              "nothing to route to", file=sys.stderr)
        return 2
    injector = _fault_injector(args)
    if injector is not None and \
            not injector.spec.wants_route_faults():
        print("error: --fault-spec on the route command wants a "
              "router scenario (replica-flaky / replica-kill)",
              file=sys.stderr)
        return 2
    try:
        router = ScanRouter(
            replicas, token=args.auth_token,
            token_header=args.token_header,
            vnodes=args.vnodes,
            capacity_factor=args.capacity_factor,
            timeout_s=args.upstream_timeout,
            fault_injector=injector)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    prober = HealthProber(router,
                          interval_s=args.probe_interval)
    prober.start()
    scaler = None
    if args.scaler != "off":
        controller = SimReplicaController() \
            if args.scaler == "sim" \
            else SubprocessReplicaController()
        policy = ScalerPolicy(
            min_replicas=max(0, args.scaler_min),
            max_replicas=max(1, args.scaler_max),
            interval_s=args.scaler_interval)
        scaler = Autoscaler(
            router, controller, policy=policy,
            verdict_fn=federated_verdicts(
                router, token=args.auth_token))
        # bring the fleet to the floor before serving
        while len(router.replicas()) < policy.min_replicas:
            name, url = controller.start()
            router.add_replica(name, url)
        scaler.start()
    front = RouterServer(router, token=args.auth_token,
                         token_header=args.token_header,
                         prober=prober, scaler=scaler)
    httpd, _ = serve_router(front, host or "127.0.0.1", int(port))
    print(f"trivy-tpu router listening on {args.listen} "
          f"(fronting {len(router.replicas())} replicas)")
    import signal
    import threading
    stop = threading.Event()

    def _term(signum, frame):
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _term)
    except ValueError:
        pass                    # not the main thread (tests)
    try:
        while not stop.wait(1.0):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        httpd.shutdown()
        front.close()
    return 0


def _admission_controller(args, server) -> tuple:
    """Mount POST /k8s/admission: an AdmissionController whose scans
    ride the server's scheduler, store (hot-swap aware), cache, and
    findings memo — warm memo entries make the common admission a
    sub-second cache hit (docs/serving.md)."""
    from .runtime import BatchScanRunner
    from .watch import AdmissionController, AdmissionPolicy
    from .watch import dir_resolver
    policy = AdmissionPolicy.parse(
        getattr(args, "admission_policy", ""),
        fail=getattr(args, "admission_fail", "open"))
    resolver = None
    if getattr(args, "images_dir", ""):
        resolver = dir_resolver(args.images_dir)
    runner = BatchScanRunner(
        store=server.store, cache=server.cache,
        # the watch command lets the operator pick the backend; the
        # server has no --backend flag and defaults to tpu
        backend=getattr(args, "backend", "tpu"),
        sched=(server.scheduler if server.scheduler is not None
               else "on"),
        # honored when this runner builds its own scheduler (the
        # --sched off server case); a shared scheduler already
        # carries the flag via _sched_config
        dispatch_depth=getattr(args, "dispatch_depth", 0) or 0,
        memo=server.memo)
    controller = AdmissionController(
        runner, store=server.store, memo=server.memo,
        policy=policy, resolver=resolver,
        default_deadline_s=getattr(args, "admission_deadline",
                                   10.0))
    return controller, runner


def run_watch(args) -> int:
    """``trivy-tpu watch``: the event-driven continuous-scanning
    runtime (docs/serving.md "Continuous scanning & admission
    control") — an event source feeds the debounced watch loop,
    scans ride the continuous-batching scheduler with the watch
    tenant identity, and (when listening) the HTTP plane serves the
    registry-notification webhook, /metrics, and /k8s/admission."""
    import signal

    from .db.compiled import SwappableStore
    from .runtime import BatchScanRunner
    from .watch import (SyntheticSource, WatchConfig, WatchLoop,
                        WebhookSource, dir_resolver,
                        make_event_storm)

    _resolve_device(args)
    try:
        store = _store(args)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    holder = SwappableStore(store)
    _trace_out(args)
    opt = _artifact_option(args)
    injector = _fault_injector(args)
    cache = _cache(args)
    if injector is not None:
        cache = injector.wrap_cache(cache)
    memo = _memo(args, cache, option=opt, injector=injector)
    try:
        sched_config = _sched_config(args)
    except ValueError as e:
        print(f"error: --tenant-config/--tenant-budget: {e}",
              file=sys.stderr)
        return 2
    runner = BatchScanRunner(
        store=holder, cache=cache, backend=args.backend,
        secret_scanner=opt.secret_scanner, sched=sched_config,
        artifact_option=opt, fault_injector=injector, memo=memo)

    targets = args.target if isinstance(args.target, list) \
        else ([args.target] if args.target else [])
    resolver = dir_resolver(args.images_dir) \
        if args.images_dir else None
    spec_text = (args.watch_source or "webhook").strip()
    kind, _, rest = spec_text.partition(":")
    if kind == "synthetic":
        if not targets:
            print("error: the synthetic source needs image-tarball "
                  "targets", file=sys.stderr)
            return 2
        kw = {"rate": 5.0, "n": 0, "seed": 20260804, "dup": 0.25}
        for pair in rest.split(","):
            pair = pair.strip()
            if not pair:
                continue
            k, eq, v = pair.partition("=")
            if not eq or k not in kw:
                print(f"error: bad --watch-source entry {pair!r}",
                      file=sys.stderr)
                return 2
            try:
                kw[k] = type(kw[k])(v)
            except (TypeError, ValueError):
                print(f"error: bad --watch-source value {v!r}",
                      file=sys.stderr)
                return 2
        source = SyntheticSource(
            targets, rate=kw["rate"], n_events=int(kw["n"]),
            seed=int(kw["seed"]), dup_rate=kw["dup"],
            tenant=args.watch_tenant, priority=args.watch_priority)
    elif kind == "webhook":
        source = WebhookSource(resolver=resolver,
                               tenant=args.watch_tenant,
                               priority=args.watch_priority)
    else:
        print(f"error: unknown --watch-source {spec_text!r} "
              "(want webhook or synthetic[:k=v,...])",
              file=sys.stderr)
        return 2

    cfg = WatchConfig(
        debounce_s=max(0.0, args.debounce_ms) / 1000.0,
        max_inflight=max(1, args.max_inflight),
        tenant=args.watch_tenant, priority=args.watch_priority,
        checkpoint_path=args.checkpoint)
    loop = WatchLoop(runner, source, cfg,
                     options=_scan_options(args))

    httpd = adm_runner = None
    if args.listen:
        from .rpc.server import ScanServer, serve
        host, _, port = args.listen.rpartition(":")
        if not port.isdigit():
            print(f"error: --listen needs host:port, got "
                  f"{args.listen!r}", file=sys.stderr)
            return 2
        server = ScanServer(store=holder, cache=cache,
                            token=args.auth_token,
                            token_header=args.token_header,
                            sched=runner.scheduler, memo=memo)
        if isinstance(source, WebhookSource):
            server.watch_source = source
        try:
            server.admission, adm_runner = _admission_controller(
                args, server)
        except ValueError as e:
            print(f"error: --admission-policy: {e}",
                  file=sys.stderr)
            return 2
        httpd, _ = serve(host or "127.0.0.1", int(port), server,
                         db_watch_prefix=args.compiled_db)
        print(f"trivy-tpu watch listening on {args.listen}",
              file=sys.stderr)
    elif memo is not None:
        # no HTTP plane constructed the memo<->store swap hook:
        # attach it here so db hot swaps still delta-re-match
        from .db.lifecycle import attach_memo
        attach_memo(holder, memo)

    if injector is not None and injector.spec.wants_event_storm():
        if not isinstance(source, WebhookSource) or not targets:
            print("error: event-storm needs the webhook source and "
                  "image-tarball targets", file=sys.stderr)
            return 2
        storm = make_event_storm(injector.spec, targets)
        # storm repositories are the target tarballs' basenames —
        # resolve them back to the listed targets (falling through
        # to the --images-dir resolver for anything else), or every
        # storm event would shed unresolvable and the drill would
        # prove nothing about debounce/backpressure
        by_ref = {os.path.basename(p): p for p in targets}
        outer = source.resolver

        def storm_resolver(ref, digest="", _outer=outer):
            hit = by_ref.get(ref.split(":")[0])
            if hit is not None:
                return hit
            return _outer(ref, digest) if _outer else None

        source.resolver = storm_resolver
        for body in storm:
            source.push_notification(body)
        print(f"fault-spec: pushed {len(storm)} storm "
              f"notifications (seed={injector.spec.seed})",
              file=sys.stderr)
        source.close()       # the storm IS the stream: drain + exit

    stop = []
    try:
        signal.signal(signal.SIGTERM,
                      lambda *_: (stop.append(1), loop.close()))
    except ValueError:
        pass                 # not the main thread (tests)
    try:
        while loop.step():
            if args.max_events and \
                    loop.counters["events"] >= args.max_events:
                break
            if stop:
                break
    except KeyboardInterrupt:
        pass
    stats = loop.drain()
    if httpd is not None:
        httpd.shutdown()
    if adm_runner is not None:
        adm_runner.close()
    runner.close()
    print(json.dumps({"watch": stats}, indent=2), file=sys.stderr)
    return 0


def run_db(args) -> int:
    if args.db_command == "update":
        return _run_db_update(args)
    if args.db_command != "build":
        print("error: unknown db subcommand", file=sys.stderr)
        return 2
    if not args.from_fixtures and not args.from_boltdb:
        print("error: --from-fixtures or --from-boltdb required",
              file=sys.stderr)
        return 2
    import time
    from .db import AdvisoryStore, CompiledDB
    store = AdvisoryStore()
    if args.from_fixtures:
        load_fixtures(
            [p for p in args.from_fixtures.split(",") if p], store)
    if args.from_boltdb:
        from .db.boltdb import CorruptDB, load_trivy_db
        t0 = time.perf_counter()
        try:
            _, n_adv, n_detail = load_trivy_db(args.from_boltdb,
                                               store)
        except (OSError, CorruptDB) as e:
            print(f"error: failed to read boltdb: {e}",
                  file=sys.stderr)
            return 1
        print(f"ingested {n_adv} advisories + {n_detail} detail "
              f"records from {args.from_boltdb} "
              f"in {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    cdb = CompiledDB.compile(store)
    compile_s = time.perf_counter() - t0
    cdb.save(args.output)
    print(f"compiled {cdb.stats['rows']} advisories "
          f"({cdb.stats['host_fallback_rows']} host-fallback, "
          f"{compile_s:.2f}s) -> {args.output}.npz")
    return 0


def _run_db_update(args) -> int:
    """`db update --from-oci-layout` (ref pkg/db/db.go:146-184)."""
    import time
    from .db.lifecycle import db_dir, update_from_oci_layout
    t0 = time.perf_counter()
    try:
        meta = update_from_oci_layout(args.from_oci_layout,
                                      args.cache_dir)
    except (OSError, ValueError) as e:
        print(f"error: db update: {e}", file=sys.stderr)
        return 1
    print(f"installed advisory DB schema v{meta.version} -> "
          f"{db_dir(args.cache_dir)} "
          f"in {time.perf_counter() - t0:.2f}s")
    if args.compile:
        from .db import AdvisoryStore, CompiledDB
        from .db.boltdb import load_trivy_db
        store = AdvisoryStore()
        _, n_adv, _ = load_trivy_db(
            os.path.join(db_dir(args.cache_dir), "trivy.db"), store)
        cdb = CompiledDB.compile(store)
        out = os.path.join(db_dir(args.cache_dir), "compiled")
        cdb.save(out)
        print(f"compiled {n_adv} advisories -> {out}.npz")
    return 0


def _severities(arg: str) -> list:
    return [Severity.parse(s) for s in arg.split(",") if s.strip()]


def _store(args):
    if getattr(args, "compiled_db", ""):
        from .db import CompiledDB
        return CompiledDB.load(args.compiled_db)
    store = AdvisoryStore()
    if args.db_fixtures:
        load_fixtures([p for p in args.db_fixtures.split(",") if p],
                      store)
    elif getattr(args, "cache_dir", ""):
        # no explicit advisory source: use the DB installed by
        # `db update` under the cache dir, honoring metadata
        # freshness (ref pkg/db/db.go:90-120; the re-download it
        # would trigger is an environment seam)
        from .db.lifecycle import db_dir, needs_update
        bolt = os.path.join(db_dir(args.cache_dir), "trivy.db")
        if os.path.exists(bolt):
            try:
                stale = needs_update(
                    args.cache_dir,
                    skip=getattr(args, "skip_db_update", False))
            except ValueError as e:
                print(f"error: advisory DB: {e}", file=sys.stderr)
                raise SystemExit(1)
            if stale:
                print("warning: advisory DB is stale (past "
                      "NextUpdate); run 'db update' or pass "
                      "--skip-db-update to silence",
                      file=sys.stderr)
            compiled = os.path.join(db_dir(args.cache_dir),
                                    "compiled")
            if os.path.exists(compiled + ".npz"):
                from .db import CompiledDB
                return CompiledDB.load(compiled)
            from .db.boltdb import load_trivy_db
            load_trivy_db(bolt, store)
    if getattr(args, "compile_db", False):
        from .db import CompiledDB
        return CompiledDB.compile(store)
    return store


def _artifact_option(args) -> ArtifactOption:
    from .secret.batch import BatchSecretScanner
    from .secret.model import load_config
    from .secret.scanner import new_scanner

    checks = args.security_checks.split(",")
    if "config" in checks:
        from .misconf import configure
        configure(
            policy_dirs=[d for d in
                         getattr(args, "config_policy",
                                 "").split(",") if d],
            helm_value_files=[f for f in
                              getattr(args, "helm_values",
                                      "").split(",") if f],
            helm_set_values=[v for v in
                             getattr(args, "helm_set",
                                     "").split(",") if v],
            trace=getattr(args, "trace", False))
    scanner = None
    if "secret" in checks:
        cpu = new_scanner(load_config(args.secret_config))
        # a process that owns no device (--backend cpu-ref, or a
        # --server client) sieves on the host engine
        backend = "tpu" if _owns_device(args) else "cpu-ref"
        scanner = BatchSecretScanner(scanner=cpu, backend=backend)
        # the rule config itself is excluded from scanning
        from .analyzer import registered_analyzers
        for a in registered_analyzers():
            if a.type == "secret":
                a.config_path = args.secret_config
    return ArtifactOption(
        skip_dirs=[d for d in args.skip_dirs.split(",") if d],
        skip_files=[f for f in args.skip_files.split(",") if f],
        file_patterns=_file_patterns(
            getattr(args, "file_patterns", None) or []),
        secret_scanner=scanner,
        scan_secrets="secret" in checks,
        scan_misconfig="config" in checks,
        scan_licenses="license" in checks,
        ingest_guards=not getattr(args, "no_ingest_guards", False),
        ingest_limits=_ingest_limits(args),
    )


def _ingest_limits(args):
    """--max-decompressed-bytes/--max-files/--ingest-deadline-s →
    ResourceLimits (None = pure defaults; zero values keep each
    default)."""
    from .guard import DEFAULT_LIMITS
    import dataclasses
    overrides = {}
    if getattr(args, "max_decompressed_bytes", 0):
        overrides["max_decompressed_bytes"] = \
            args.max_decompressed_bytes
    if getattr(args, "max_files", 0):
        overrides["max_files"] = args.max_files
    if getattr(args, "ingest_deadline_s", 0.0):
        overrides["ingest_deadline_s"] = args.ingest_deadline_s
    if not overrides:
        return None
    return dataclasses.replace(DEFAULT_LIMITS, **overrides)


def _file_patterns(pairs) -> dict:
    """--file-patterns TYPE:REGEX pairs → {analyzer type: regex}
    (ref analyzer.go:451-469 CreateFilePatterns: split on the first
    colon, reject malformed pairs, compile eagerly so a bad regex
    fails the run up front). Repeats for one type OR with '|'."""
    import re as _re
    if isinstance(pairs, str):          # env/config-file spelling
        pairs = [p for p in pairs.split(",") if p]
    out: dict = {}
    for pair in pairs:
        atype, sep, pattern = pair.partition(":")
        if not sep or not atype or not pattern:
            raise ValueError(
                f"invalid file pattern {pair!r} "
                "(want TYPE:REGEX, e.g. dockerfile:Customfile)")
        try:
            _re.compile(pattern)
        except _re.error as e:
            raise ValueError(
                f"invalid file pattern regex {pattern!r}: {e}")
        # non-capturing groups keep each alternative self-contained
        # (a bare '|' join would let an inline flag in one pattern
        # leak into — or break compilation of — the others)
        out[atype] = f"{out[atype]}|(?:{pattern})" \
            if atype in out else f"(?:{pattern})"
    for combined in out.values():
        _re.compile(combined)       # the joined form must compile too
    return out


_SBOM_FORMATS = ("cyclonedx", "spdx", "spdx-json", "github")


def _scan_options(args) -> ScanOptions:
    return ScanOptions(
        vuln_type=[v for v in args.vuln_type.split(",") if v],
        security_checks=[c for c in
                         args.security_checks.split(",") if c],
        # SBOM interchange formats need the full package inventory
        # (ref pkg/commands/artifact/run.go ListAllPkgs override)
        # the tree renders from Result.Packages, so it implies the
        # full inventory (ref report_flags.go ListAllPkgs override)
        list_all_packages=args.list_all_pkgs or
        getattr(args, "dependency_tree", False) or
        args.format in _SBOM_FORMATS,
        scan_removed_packages=getattr(args, "removed_pkgs", False),
        backend="cpu-ref" if args.backend == "cpu-ref" else args.backend,
    )


def _finish(args, report: Report) -> int:
    from .scan.filter import IgnorePolicyError, load_ignore_policy
    try:
        policy = load_ignore_policy(
            getattr(args, "ignore_policy", ""))
        results = filter_results(
            report.results, _severities(args.severity),
            ignore_unfixed=args.ignore_unfixed,
            ignored_ids=load_ignore_file(args.ignorefile),
            policy=policy,
            include_non_failures=getattr(
                args, "include_non_failures", False))
    except (OSError, IgnorePolicyError) as e:
        # a broken user policy fails cleanly, like the reference's
        # Rego eval errors; unrelated bugs keep their traceback
        print(f"error: ignore policy failed: {e}", file=sys.stderr)
        return 1
    # the reference never drops emptied results — a filtered-out or
    # finding-free result stays as a husk (filter.go mutates in
    # place; spring4shell-*.json.golden keep the empty os-pkgs and
    # custom entries)
    report.results = results
    out = open(args.output, "w") if args.output else sys.stdout
    try:
        write_report(report, fmt=args.format, output=out,
                     severities=[str(s) for s in
                                 _severities(args.severity)],
                     app_version=__version__,
                     output_template=getattr(args, "template", ""),
                     dependency_tree=getattr(
                         args, "dependency_tree", False))
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        if args.output:
            out.close()
    if args.exit_code and any(r.failed() for r in report.results):
        return args.exit_code
    return 0


def _custom_headers(args) -> dict:
    out = {}
    for pair in (getattr(args, "custom_headers", "") or "").split(","):
        if "=" in pair:
            k, _, v = pair.partition("=")
            out[k.strip()] = v.strip()
    return out


def _cache(args):
    if getattr(args, "server", ""):
        # client/server split: blobs push to the server's cache
        # (ref run.go:296-299 NopCache(RemoteCache)). Deliberately
        # NOT behind ResilientCache: the reader of these blobs is
        # the REMOTE server, so degrading a put into a local
        # fallback would let the later Scan RPC silently scan with
        # missing layers. The cache and scan RPCs share fate (same
        # server), and the client's own backoff loop already covers
        # transient failures — loud failure is the correct mode.
        from .rpc.client import RemoteCache
        return RemoteCache(args.server, token=args.auth_token,
                           token_header=args.token_header,
                           custom_headers=_custom_headers(args))
    backend = getattr(args, "cache_backend", "fs")
    # remote backends go behind the circuit breaker: construction
    # failures (bad URL, unreachable at startup) still fail the run
    # fast, but a mid-scan outage degrades to the local fallback
    # instead of killing the fleet (docs/robustness.md)
    if backend.startswith("redis://"):
        from .artifact.redis_cache import RedisCache
        from .artifact.resilient import ResilientCache
        return ResilientCache(RedisCache(backend))
    if backend.startswith("s3://"):
        from .artifact.s3_cache import S3Cache
        from .artifact.resilient import ResilientCache
        return ResilientCache(S3Cache(backend))
    if backend != "fs":
        raise ValueError(
            f"unsupported --cache-backend {backend!r} "
            "(use 'fs', redis://host:port, or "
            "s3://bucket/prefix?endpoint=...)")
    from .artifact.cache import MemoryCache
    if args.no_cache:
        return MemoryCache()
    return FSCache(args.cache_dir)


def _rpc_error():
    from .rpc.client import RPCError
    return RPCError


def _memo(args, cache=None, option=None, injector=None):
    """--memo wiring: a FindingsMemo over the blob-cache tier
    (docs/performance.md "Findings memoization"), or None under
    --no-memo / vuln-free scans. The memo backend mirrors
    --cache-backend unless --memo-cache overrides it."""
    if getattr(args, "no_memo", False):
        return None
    checks = [c for c in getattr(args, "security_checks",
                                 "vuln").split(",") if c]
    if "vuln" not in checks:
        return None
    from .memo import make_findings_memo
    backend = getattr(args, "backend", "tpu")
    return make_findings_memo(
        cache=cache, cache_dir=getattr(args, "cache_dir", ""),
        uri=getattr(args, "memo_cache", ""),
        artifact_option=option, fault_injector=injector,
        backend="cpu-ref" if backend == "cpu-ref" else "tpu")


def _scanner(args, cache, option=None):
    """Local or remote scan driver — the client needs no DB when a
    server is set (ref run.go:269-271 initDB skipped), and a scan
    without vuln checks (e.g. the config command) skips advisory
    DB loading entirely (ref app.go:533 omits DBFlagGroup)."""
    if getattr(args, "server", ""):
        from .rpc.client import RemoteScanner
        return RemoteScanner(args.server, token=args.auth_token,
                             token_header=args.token_header,
                             custom_headers=_custom_headers(args))
    checks = [c for c in getattr(args, "security_checks",
                                 "vuln").split(",") if c]
    if "vuln" not in checks:
        return LocalScanner(cache, AdvisoryStore())
    return LocalScanner(cache, _store(args),
                        memo=_memo(args, cache, option=option))


def run_image(args) -> int:
    targets = args.target if isinstance(args.target, list) \
        else ([args.target] if args.target else [])
    if len(targets) > 1:
        if args.input:
            # silently dropping --input next to a target list would
            # scan a different fleet than the user asked for
            print("error: --input cannot be combined with multiple "
                  "image targets; list the archive as a target "
                  "instead", file=sys.stderr)
            return 2
        return _run_image_batch(args, targets)
    if _reject_unwired_fault_spec(args):
        return 2
    target = targets[0] if targets else ""
    args.target = target
    path = args.input or target
    if not path:
        print("error: image target or --input required",
              file=sys.stderr)
        return 2
    _resolve_device(args)
    opt = _artifact_option(args)
    from .guard import make_budget
    budget = make_budget(opt.ingest_limits,
                         enabled=opt.ingest_guards, name=path)
    try:
        if args.input:
            # an explicit archive path must fail as a file error,
            # never fall through to daemon/registry resolution
            image = load_image(args.input,
                               name=args.target or args.input,
                               budget=budget)
        else:
            from .artifact.resolve import resolve_image
            image = resolve_image(path, name=args.target or path,
                                  budget=budget)
    except (OSError, ValueError, tarfile_error) as e:
        print(f"error: failed to load image {path!r}: {e}",
              file=sys.stderr)
        return 1
    cache = _cache(args)
    artifact = ImageArtifact(image, cache, option=opt,
                             budget=budget)
    try:
        ref = artifact.inspect()
        scanner = _scanner(args, cache, option=opt)
        results, os_found = scanner.scan(
            ScanTarget(name=ref.name, artifact_id=ref.id,
                       blob_ids=ref.blob_ids),
            _scan_options(args))
    except _rpc_error() as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        getattr(image, "cleanup", lambda: None)()

    report = Report(
        artifact_name=ref.name,
        artifact_type="container_image",
        metadata=Metadata(
            os=os_found,
            image_id=ref.image_metadata.id,
            diff_ids=ref.image_metadata.diff_ids,
            repo_tags=ref.image_metadata.repo_tags,
            repo_digests=ref.image_metadata.repo_digests,
            image_config=ref.image_metadata.image_config,
        ),
        results=results,
    )
    budget = getattr(artifact, "budget", None)
    if budget is not None and budget.soft_faults:
        # survivable hostile input (docs/robustness.md): report the
        # scan degraded with ingest-stage causes, keep exit 0
        report.mark_degraded(
            [{"stage": "ingest", "kind": k, "message": m}
             for k, m in budget.soft_faults])
        for k, m in budget.soft_faults:
            print(f"warning: {ref.name}: degraded (ingest/{k}): {m}",
                  file=sys.stderr)
    return _finish(args, report)


def _fault_injector(args):
    """--fault-spec → FaultInjector, or None. Parse errors fail the
    run up front (ValueError is caught by main's clean-error path)."""
    spec = getattr(args, "fault_spec", "")
    if not spec:
        return None
    from .faults import FaultInjector, parse_fault_spec
    return FaultInjector(parse_fault_spec(spec))


def _reject_unwired_fault_spec(args) -> bool:
    """True (and an error printed) when --fault-spec was given on a
    path that has no injection sites — a clean run there would be
    false confidence, not a passed drill (docs/robustness.md)."""
    if getattr(args, "fault_spec", ""):
        print("error: --fault-spec is wired into multi-target "
              "image scans, the server, and the route command; "
              "this command would inject nothing", file=sys.stderr)
        return True
    return False


def _sched_config(args):
    from .runtime.ring import resolve_dispatch_depth
    from .sched import SchedConfig, parse_tenant_config
    tenancy = None
    if getattr(args, "tenant_config", ""):
        # a typo'd tenant table must fail the run up front — a
        # malformed QoS config silently granting unlimited service
        # is exactly the overload hole tenancy exists to close
        tenancy = parse_tenant_config(args.tenant_config)
    budgets = None
    if getattr(args, "tenant_budget", ""):
        # same eager-validation contract: a typo'd budget silently
        # metering nothing would defeat the admission gate
        from .obs.cost import parse_budget_config
        budgets = parse_budget_config(args.tenant_budget)
    return SchedConfig(
        max_queue=getattr(args, "sched_queue", 256),
        workers=getattr(args, "sched_workers", 4),
        flush_timeout_s=getattr(args, "sched_flush_ms", 50.0)
        / 1000.0,
        dispatch_depth=resolve_dispatch_depth(
            getattr(args, "dispatch_depth", 0) or 0),
        tenancy=tenancy,
        budgets=budgets)


def _init_multihost(args) -> int:
    """Join the pod when ``--coordinator``/``--num-processes``/
    ``--process-id`` or the TRIVY_TPU_* env describe one (the
    jax.distributed seam, docs/performance.md §8). Returns 0, or 2
    on a malformed topology. Must run before any jax backend touch
    so jax.devices() becomes the global set."""
    from .parallel.multihost import initialize, topology_from_env
    try:
        topo = topology_from_env(
            coordinator=getattr(args, "coordinator", ""),
            num_processes=getattr(args, "num_processes", 0) or 0,
            process_id=(getattr(args, "process_id", -1)
                        if getattr(args, "process_id", -1)
                        is not None else -1))
        if topo.multi_host:
            initialize(topo)
            print(f"multi-host: process {topo.process_id}/"
                  f"{topo.num_processes} joined via "
                  f"{topo.coordinator}", file=sys.stderr)
    except (ValueError, RuntimeError) as e:
        print(f"error: multi-host topology: {e}", file=sys.stderr)
        return 2
    return 0


def _run_image_batch(args, targets: list) -> int:
    """``image a.tar b.tar ...``: the fleet path — every target
    routes through the continuous-batching scheduler (``--sched off``
    keeps the direct single-batch ladder for differential runs). A
    target that is no file and reads as a reference (``image
    host/repo:tag ...``) is pulled over the Distribution API inside
    its request (``BatchScanRunner.scan_paths``)."""
    if getattr(args, "server", ""):
        print("error: multi-image batch scan is local-only; scan "
              "one target at a time against --server",
              file=sys.stderr)
        return 2
    if args.format not in ("table", "json", "template"):
        # per-slot writers would concatenate complete documents into
        # one stream — invalid sarif/SBOM output; refuse up front
        print(f"error: multi-image scans support table/json/"
              f"template output, not {args.format}",
              file=sys.stderr)
        return 2
    checks = [c for c in args.security_checks.split(",") if c]
    store = _store(args) if "vuln" in checks else AdvisoryStore()
    opt = _artifact_option(args)
    injector = _fault_injector(args)
    cache = _cache(args)
    if injector is not None:
        cache = injector.wrap_cache(cache)
    hostile_dir = ""
    if injector is not None and injector.spec.hostile:
        # hostile-ingest drill (docs/robustness.md): materialize the
        # seeded adversarial corpus and append it to the fleet — the
        # guard layer must quarantine each hostile slot per-target
        # while the listed targets complete untouched
        import tempfile
        from .faults.hostile import build_corpus
        hostile_dir = tempfile.mkdtemp(prefix="trivy-tpu-hostile-")
        extra = build_corpus(hostile_dir, seed=injector.spec.seed,
                             only=list(injector.spec.hostile))
        targets = list(targets) + [p for _, p in extra]
        print(f"fault-spec: added {len(extra)} hostile artifacts "
              f"to the fleet (seed={injector.spec.seed})",
              file=sys.stderr)
    trace_out = _trace_out(args)
    try:
        sched_config = _sched_config(args)
    except ValueError as e:
        print(f"error: --tenant-config/--tenant-budget: {e}",
              file=sys.stderr)
        return 2
    rc = _init_multihost(args)
    if rc:
        return rc
    _resolve_device(args)
    runner = _batch_runner(args, store, opt, cache, sched_config,
                           injector)
    options = _scan_options(args)
    if injector is not None and injector.spec.deadline_s > 0:
        # deadline-storm scenario: the spec carries the per-request
        # deadline, the harness applies it
        options.deadline_s = injector.spec.deadline_s
    try:
        results = runner.scan_paths(targets, options)
        stats = runner.last_stats
    finally:
        runner.close()
        if hostile_dir:
            import shutil
            shutil.rmtree(hostile_dir, ignore_errors=True)
    if getattr(args, "sched_stats", False):
        dump = _process_stats(stats.get("sched", stats))
        if injector is not None:
            dump["faults"] = injector.stats()
        print(json.dumps(dump, indent=2), file=sys.stderr)
    if trace_out:
        from .obs import get_tracer
        print(f"traces written to {trace_out} "
              f"({get_tracer().n_exported} total this process)",
              file=sys.stderr)
    return _finish_many(args, results)


def _batch_runner(args, store, opt, cache, sched_config,
                  injector=None, warm: bool = True):
    """The fleet runner as the command's flags configure it: for
    several images (``--sched`` chooses its path) and for a tree."""
    from .runtime import BatchScanRunner
    checks = [c for c in args.security_checks.split(",") if c]
    return BatchScanRunner(
        store=store, cache=cache, backend=args.backend,
        secret_scanner=opt.secret_scanner,
        sched=("on" if args.sched == "on" else "off"),
        sched_config=sched_config,
        artifact_option=opt,
        fault_injector=injector,
        dispatch_depth=getattr(args, "dispatch_depth", 0) or 0,
        warm=warm,
        memo=_memo(args, cache, option=opt, injector=injector)
        if "vuln" in checks else None)


def _process_stats(stats: dict) -> dict:
    """The ``--sched-stats`` dump: the run's own stats plus the
    process-wide books, so the dump names the device the kernels
    ran on and counts the device work on both execution paths. The
    scheduler's snapshot already carries the detect/secret/dispatch
    books; the direct path's phase stats gain them here (its
    per-batch sieve stats move to ``secret_batch``)."""
    from .ops.program import compiled_programs
    from .runtime.aot import COMPILE_CACHE_METRICS
    from .runtime.device import device_identity
    dump = dict(stats)
    if "counters" not in dump:
        from .detect.metrics import DETECT_METRICS
        from .runtime.ring import RING_METRICS
        from .secret.metrics import SECRET_METRICS
        dump["secret_batch"] = dump.pop("secret", {})
        dump["secret"] = SECRET_METRICS.snapshot()
        dump["detect"] = DETECT_METRICS.snapshot()
        dump["dispatch"] = RING_METRICS.snapshot()
    dump["compile_cache"] = COMPILE_CACHE_METRICS.snapshot()
    dump["programs"] = compiled_programs()
    dump["device"] = device_identity()
    return dump


def _trace_out(args) -> str:
    """--trace-out: point the process tracer's exporter at the
    directory (created if missing); every completed request trace
    lands there as Perfetto-loadable trace-event JSON."""
    trace_out = getattr(args, "trace_out", "")
    if trace_out:
        from .obs import get_tracer
        os.makedirs(trace_out, exist_ok=True)
        get_tracer().export_dir = trace_out
    return trace_out


def _finish_many(args, results) -> int:
    """Render one report per batch slot: json emits a single array
    (fleet reports are machine-read), other formats append to the
    same stream. Exit code: flag-driven like _finish; slot errors
    (load failure, deadline) report on stderr and exit 1."""
    from .scan.filter import IgnorePolicyError, load_ignore_policy
    try:
        policy = load_ignore_policy(
            getattr(args, "ignore_policy", ""))
    except (OSError, IgnorePolicyError) as e:
        print(f"error: ignore policy failed: {e}", file=sys.stderr)
        return 1
    ignored = load_ignore_file(args.ignorefile)
    severities = _severities(args.severity)
    code = 0
    docs = []
    out = open(args.output, "w") if args.output else sys.stdout
    try:
        for res in results:
            if res.error:
                print(f"error: {res.name}: {res.error}",
                      file=sys.stderr)
                code = max(code, 1)
                continue
            if getattr(res, "status", "ok") == "degraded":
                # degraded slot: the report is complete and correct
                # (host fallback) — annotate on stderr, keep exit 0
                causes = "; ".join(
                    f"{c.stage}/{c.kind}" for c in res.causes)
                print(f"warning: {res.name}: degraded ({causes})",
                      file=sys.stderr)
            report = res.report
            try:
                report.results = filter_results(
                    report.results, severities,
                    ignore_unfixed=args.ignore_unfixed,
                    ignored_ids=ignored, policy=policy,
                    include_non_failures=getattr(
                        args, "include_non_failures", False))
            except IgnorePolicyError as e:
                print(f"error: ignore policy failed: {e}",
                      file=sys.stderr)
                return 1
            if args.format == "json":
                import io as _io
                buf = _io.StringIO()
                write_report(report, fmt="json", output=buf,
                             severities=[str(s)
                                         for s in severities],
                             app_version=__version__)
                docs.append(json.loads(buf.getvalue()))
            else:
                write_report(
                    report, fmt=args.format, output=out,
                    severities=[str(s) for s in severities],
                    app_version=__version__,
                    output_template=getattr(args, "template", ""),
                    dependency_tree=getattr(args, "dependency_tree",
                                            False))
            if args.exit_code and \
                    any(r.failed() for r in report.results):
                code = args.exit_code
        if args.format == "json":
            json.dump(docs, out, indent=2)
            out.write("\n")
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        if args.output:
            out.close()
    return code


def run_sbom(args) -> int:
    """Scan an SBOM file (ref pkg/commands/artifact/run.go sbomScanner:
    vulnerability checks only). Several documents (more targets than
    one, or a directory) take the batch form."""
    from .artifact.sbom import SBOMArtifact
    targets = args.target if isinstance(args.target, list) \
        else [args.target]
    if len(targets) > 1 or os.path.isdir(targets[0]):
        return _run_sbom_batch(args, targets)
    args.target = targets[0]
    if _reject_unwired_fault_spec(args):
        return 2
    if not os.path.isfile(args.target):
        print(f"error: no such file: {args.target}", file=sys.stderr)
        return 1
    _resolve_device(args)
    cache = _cache(args)
    # vuln-only scan: no analyzers or secret stack needed
    artifact = SBOMArtifact(args.target, cache,
                            option=ArtifactOption(scan_secrets=False))
    try:
        ref = artifact.inspect()
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    options = _scan_options(args)
    options.security_checks = ["vuln"]
    try:
        results, os_found = _scanner(args, cache).scan(
            ScanTarget(name=ref.name, artifact_id=ref.id,
                       blob_ids=ref.blob_ids),
            options)
    except _rpc_error() as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    report = Report(
        artifact_name=args.target,
        artifact_type=ref.type,
        metadata=Metadata(os=os_found),
        results=results,
        cyclonedx=ref.cyclonedx,
    )
    return _finish(args, report)


# documents one ``scan_boms`` call of the batch form takes: the
# source's own batch (BASELINE.json configs[3]), and what bounds the
# bytes, blobs and reports the command holds at once
SBOM_CALL_DOCS = 10_000


def _sbom_paths(targets: list) -> list:
    """The batch form's documents: every target that is a file, and
    under one that is a directory every ``*.json`` (CycloneDX and
    SPDX JSON alike: ``a.cdx.json``, ``b.spdx.json``), in sorted
    order. Raises ``OSError`` for a target that is neither."""
    paths = []
    for t in targets:
        if os.path.isdir(t):
            for root, dirs, files in os.walk(t):
                dirs.sort()
                paths += [os.path.join(root, f) for f in sorted(files)
                          if f.endswith(".json")]
        elif os.path.isfile(t):
            paths.append(t)
        else:
            raise FileNotFoundError(f"no such file or directory: {t}")
    return paths


def _sbom_calls(runner, paths: list, options):
    """The results of ``paths``, document for document, from
    ``scan_boms`` calls of at most ``SBOM_CALL_DOCS``: a call's
    documents are read when its turn comes and let go when its
    results have been taken. A file that cannot be read fails its
    own slot, as a document that cannot be decoded does."""
    from .artifact.cache import MemoryCache
    from .obs.trace import phase_span
    from .runtime import BatchScanResult
    for a in range(0, len(paths), SBOM_CALL_DOCS):
        call = paths[a:a + SBOM_CALL_DOCS]
        # a call's blobs live as long as the call: ``scan_boms``
        # decodes every document it is given and nothing reads a
        # blob again, so no cache tier is written (--cache-backend,
        # --cache-dir choose nothing here)
        runner.cache = MemoryCache()
        boms, unread = [], {}
        with phase_span("read", pipeline="sbom", docs=len(call)):
            for k, path in enumerate(call):
                try:
                    with open(path, "rb") as f:
                        boms.append((path, f.read()))
                except OSError as e:
                    unread[k] = BatchScanResult(
                        name=path, error=str(e)).mark_failed(
                            "host", "load_failed", str(e))
        scanned = iter(runner.scan_boms(boms, options))
        del boms
        for k in range(len(call)):
            yield unread[k] if k in unread else next(scanned)


def _run_sbom_batch(args, targets: list) -> int:
    """``sbom a.cdx.json b.cdx.json ...`` or ``sbom DIR``: every
    document through ``BatchScanRunner.scan_boms`` on the direct
    ladder (the documents are all known, so one dedup a call over
    all their jobs beats a request a document; ``--sched`` chooses
    nothing here, as for a tree), reported a document as ``image
    a.tar b.tar`` reports an image."""
    if _reject_unwired_fault_spec(args):
        return 2
    if getattr(args, "server", ""):
        print("error: multi-document sbom scan is local-only; scan "
              "one document at a time against --server",
              file=sys.stderr)
        return 2
    if args.format not in ("table", "json", "template"):
        print(f"error: multi-document scans support table/json/"
              f"template output, not {args.format}",
              file=sys.stderr)
        return 2
    try:
        paths = _sbom_paths(targets)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if not paths:
        print(f"error: no *.json document under "
              f"{', '.join(targets)}", file=sys.stderr)
        return 1
    _resolve_device(args)
    from .runtime import BatchScanRunner
    runner = BatchScanRunner(store=_store(args),
                             backend=args.backend, sched="off")
    options = _scan_options(args)
    options.security_checks = ["vuln"]
    try:
        code = _finish_many(args, _sbom_calls(runner, paths, options))
    finally:
        runner.close()
    if getattr(args, "sched_stats", False):
        from .obs.trace import phase_rows
        dump = _process_stats(runner.last_stats)
        dump["sbom"] = {"documents": len(paths),
                        "phase": phase_rows("sbom")}
        print(json.dumps(dump, indent=2), file=sys.stderr)
    return code


def run_repo(args) -> int:
    """Scan a git repository (ref pkg/fanal/artifact/remote)."""
    from .artifact.remote import GitError, RemoteRepoArtifact
    if _reject_unwired_fault_spec(args):
        return 2
    _resolve_device(args)
    cache = _cache(args)
    artifact = RemoteRepoArtifact(
        args.target, cache, option=_artifact_option(args),
        branch=args.branch, tag=args.tag, commit=args.commit)
    try:
        try:
            ref = artifact.inspect()
        except GitError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        try:
            results, os_found = _scanner(args, cache).scan(
                ScanTarget(name=ref.name, artifact_id=ref.id,
                           blob_ids=ref.blob_ids),
                _scan_options(args))
        except _rpc_error() as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
    finally:
        artifact.clean()
    report = Report(
        artifact_name=args.target,
        artifact_type="repository",
        metadata=Metadata(os=os_found),
        results=results,
    )
    return _finish(args, report)


def run_fs(args) -> int:
    if _reject_unwired_fault_spec(args):
        return 2
    if not os.path.isdir(args.target):
        print(f"error: no such directory: {args.target}",
              file=sys.stderr)
        return 1
    _resolve_device(args)
    checks = args.security_checks.split(",")
    if not getattr(args, "server", "") and \
            ("secret" in checks or "vuln" in checks):
        return _run_fs_scheduled(args)
    # the direct path: a --server client, which owns no device and
    # pushes its blob to the server's cache, and a scan with nothing
    # for the device (``config``, licences alone), which needs no
    # scheduler
    cache = _cache(args)
    artifact = LocalFSArtifact(args.target, cache,
                               option=_artifact_option(args))
    try:
        ref = artifact.inspect()
        results, os_found = _scanner(args, cache).scan(
            ScanTarget(name=ref.name, artifact_id=ref.id,
                       blob_ids=ref.blob_ids),
            _scan_options(args))
    except _rpc_error() as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    report = Report(
        artifact_name=args.target,
        artifact_type="filesystem",
        metadata=Metadata(os=os_found),
        results=results,
    )
    return _finish(args, report)


def _run_fs_scheduled(args) -> int:
    """``fs``/``rootfs`` with secret or vulnerability checks, on
    the scheduler: the tree is one request of
    ``BatchScanRunner.scan_trees``, whose secret candidates stream
    through the sieve in parts while the walk goes on
    (``submit_tree``); the report is the direct path's."""
    checks = [c for c in args.security_checks.split(",") if c]
    store = _store(args) if "vuln" in checks else AdvisoryStore()
    cache = _cache(args)
    try:
        sched_config = _sched_config(args)
    except ValueError as e:
        print(f"error: --tenant-config/--tenant-budget: {e}",
              file=sys.stderr)
        return 2
    # one tree and the process ends: the rungs it meets compile (or
    # come from the persistent cache) as it meets them, as on the
    # direct path before; warming the whole ladder first would cost
    # every push's scan seconds it cannot win back
    runner = _batch_runner(args, store, _artifact_option(args),
                           cache, sched_config, warm=False)
    try:
        res = runner.scan_trees([args.target],
                                _scan_options(args))[0]
        stats = runner.last_stats
    finally:
        runner.close()
    if getattr(args, "sched_stats", False):
        print(json.dumps(_process_stats(stats["sched"]), indent=2),
              file=sys.stderr)
    if res.error:
        print(f"error: {res.name}: {res.error}", file=sys.stderr)
        return 1
    if res.status == "degraded":
        causes = "; ".join(f"{c.stage}/{c.kind}" for c in res.causes)
        print(f"warning: {res.name}: degraded ({causes})",
              file=sys.stderr)
    return _finish(args, res.report)


if __name__ == "__main__":
    sys.exit(main())
