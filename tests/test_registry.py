"""Distribution-API client vs an in-process fake registry
(reference: pkg/fanal/image/remote.go + token auth; the reference's
integration suite uses a testcontainers auth registry — here the
registry is an in-process HTTP server, same protocol)."""

import base64
import gzip
import hashlib
import io
import json
import tarfile
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from trivy_tpu.artifact.registry import (MT_MANIFEST,
                                         MT_MANIFEST_LIST,
                                         DistributionClient,
                                         RegistryError, parse_ref)
from trivy_tpu.artifact.walker import collect_layer_tar


def _layer_tar(files: dict) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for path, content in files.items():
            ti = tarfile.TarInfo(path)
            ti.size = len(content)
            tf.addfile(ti, io.BytesIO(content))
    return gzip.compress(buf.getvalue())


class FakeRegistry:
    """Minimal /v2 registry: one repo, manifest list + amd64/arm64
    manifests, optional bearer-token auth."""

    def __init__(self, require_auth=False, user="u", password="p"):
        self.require_auth = require_auth
        self.user, self.password = user, password
        self.blobs = {}
        self.manifests = {}
        self.token = "tok-" + hashlib.sha256(b"x").hexdigest()[:8]
        self._build()

    def put_blob(self, data: bytes) -> dict:
        digest = "sha256:" + hashlib.sha256(data).hexdigest()
        self.blobs[digest] = data
        return {"digest": digest, "size": len(data)}

    def _build(self):
        layer = _layer_tar({
            "etc/alpine-release": b"3.16.2\n",
            "lib/apk/db/installed":
                b"P:musl\nV:1.2.2-r0\no:musl\n\n"})
        diff_id = "sha256:" + hashlib.sha256(
            gzip.decompress(layer)).hexdigest()
        ldesc = self.put_blob(layer)
        ldesc["mediaType"] = \
            "application/vnd.docker.image.rootfs.diff.tar.gzip"
        config = json.dumps({
            "architecture": "amd64", "os": "linux",
            "rootfs": {"type": "layers", "diff_ids": [diff_id]},
            "config": {}}).encode()
        cdesc = self.put_blob(config)
        cdesc["mediaType"] = \
            "application/vnd.docker.container.image.v1+json"
        manifest = json.dumps({
            "schemaVersion": 2, "mediaType": MT_MANIFEST,
            "config": cdesc, "layers": [ldesc]}).encode()
        mdigest = "sha256:" + hashlib.sha256(manifest).hexdigest()
        self.manifests["1.0"] = (MT_MANIFEST, manifest)
        self.manifests[mdigest] = (MT_MANIFEST, manifest)
        index = json.dumps({
            "schemaVersion": 2, "mediaType": MT_MANIFEST_LIST,
            "manifests": [
                {"digest": "sha256:" + "0" * 64, "mediaType":
                 MT_MANIFEST,
                 "platform": {"os": "linux",
                              "architecture": "arm64"}},
                {"digest": mdigest, "mediaType": MT_MANIFEST,
                 "platform": {"os": "linux",
                              "architecture": "amd64"}},
            ]}).encode()
        self.manifests["multi"] = (MT_MANIFEST_LIST, index)

    def start(self):
        reg = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _auth_ok(self):
                if not reg.require_auth:
                    return True
                auth = self.headers.get("Authorization", "")
                return auth == f"Bearer {reg.token}"

            def do_GET(self):
                if self.path.startswith("/token"):
                    auth = self.headers.get("Authorization", "")
                    want = "Basic " + base64.b64encode(
                        f"{reg.user}:{reg.password}".encode()
                    ).decode()
                    if auth != want:
                        self.send_response(401)
                        self.end_headers()
                        return
                    body = json.dumps({"token": reg.token}).encode()
                    self.send_response(200)
                    self.send_header("Content-Length",
                                     str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if not self._auth_ok():
                    self.send_response(401)
                    self.send_header(
                        "WWW-Authenticate",
                        f'Bearer realm="http://{self.headers["Host"]}'
                        f'/token",service="fake",'
                        f'scope="repository:org/app:pull"')
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                parts = self.path.split("/")
                body, ctype = None, "application/octet-stream"
                if "/manifests/" in self.path:
                    ref = parts[-1]
                    if ref in reg.manifests:
                        ctype, body = reg.manifests[ref]
                elif "/blobs/" in self.path:
                    body = reg.blobs.get(parts[-1])
                if body is None:
                    self.send_response(404)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        t = threading.Thread(target=self.httpd.serve_forever,
                             daemon=True)
        t.start()
        return self

    def stop(self):
        self.httpd.shutdown()


class TestParseRef:
    def test_hub_shorthand(self):
        assert parse_ref("alpine:3.16") == \
            ("index.docker.io", "library/alpine", "3.16")

    def test_registry_port_and_digest(self):
        assert parse_ref(
            "127.0.0.1:5000/org/app@sha256:" + "a" * 64) == \
            ("127.0.0.1:5000", "org/app", "sha256:" + "a" * 64)

    def test_default_tag(self):
        assert parse_ref("ghcr.io/org/app") == \
            ("ghcr.io", "org/app", "latest")


def _scan_src(src):
    """The pulled source must walk like any other image."""
    names = []
    for layer in src.layers:
        with layer.open() as tf:
            files, _opq, _wh = collect_layer_tar(tf)
            names.extend(path for path, _size, _read in files)
    return names


class TestPull:
    def test_pull_no_auth(self):
        reg = FakeRegistry().start()
        try:
            c = DistributionClient()
            src = c.pull(f"127.0.0.1:{reg.port}/org/app:1.0")
            assert "lib/apk/db/installed" in _scan_src(src)
            src.cleanup()
        finally:
            reg.stop()

    def test_pull_manifest_list_platform_select(self):
        reg = FakeRegistry().start()
        try:
            c = DistributionClient(platform="linux/amd64")
            src = c.pull(f"127.0.0.1:{reg.port}/org/app:multi")
            assert "etc/alpine-release" in _scan_src(src)
            src.cleanup()
            with pytest.raises(RegistryError, match="platform"):
                DistributionClient(platform="linux/s390x").pull(
                    f"127.0.0.1:{reg.port}/org/app:multi")
        finally:
            reg.stop()

    def test_pull_with_token_auth(self):
        reg = FakeRegistry(require_auth=True).start()
        try:
            c = DistributionClient(auth=("u", "p"))
            src = c.pull(f"127.0.0.1:{reg.port}/org/app:1.0")
            assert "lib/apk/db/installed" in _scan_src(src)
            src.cleanup()
        finally:
            reg.stop()

    def test_bad_credentials_rejected(self):
        reg = FakeRegistry(require_auth=True).start()
        try:
            c = DistributionClient(auth=("u", "wrong"))
            with pytest.raises(RegistryError, match="401"):
                c.pull(f"127.0.0.1:{reg.port}/org/app:1.0")
        finally:
            reg.stop()

    def test_resolve_chain_reaches_registry(self):
        """resolve_image falls through archive/daemon to the
        registry client and scans the pulled image end-to-end."""
        from trivy_tpu.artifact.resolve import (DaemonClient,
                                                RegistryClient,
                                                resolve_image)
        reg = FakeRegistry().start()
        try:
            src = resolve_image(
                f"127.0.0.1:{reg.port}/org/app:1.0",
                daemon=DaemonClient(sockets=()),
                registry=RegistryClient())
            assert src.config["rootfs"]["diff_ids"]
            src.cleanup()
        finally:
            reg.stop()

    def test_unreachable_registry_clean_error(self):
        from trivy_tpu.artifact.resolve import (DaemonClient,
                                                ResolveError,
                                                RegistryClient,
                                                resolve_image)
        with pytest.raises(ResolveError, match="unreachable"):
            resolve_image("127.0.0.1:1/org/app:1.0",
                          daemon=DaemonClient(sockets=()),
                          registry=RegistryClient())


class TestReviewFixes:
    def test_layout_index_records_image_manifest_type(self):
        reg = FakeRegistry().start()
        try:
            c = DistributionClient(platform="linux/amd64")
            src = c.pull(f"127.0.0.1:{reg.port}/org/app:multi")
            # reach into the written layout through the source's
            # cleanup closure is fragile; re-read via the blobs dir
            import glob
            layouts = glob.glob("/tmp/trivy-tpu-pull-*/index.json")
            newest = max(layouts, key=lambda p: __import__("os")
                         .path.getmtime(p))
            idx = json.load(open(newest))
            assert idx["manifests"][0]["mediaType"] == MT_MANIFEST
            src.cleanup()
        finally:
            reg.stop()

    def test_malformed_manifest_clean_resolve_error(self):
        from trivy_tpu.artifact.resolve import (DaemonClient,
                                                ResolveError,
                                                RegistryClient,
                                                resolve_image)
        reg = FakeRegistry().start()
        # break the manifest: schema-1 style, no 'config'
        reg.manifests["1.0"] = (MT_MANIFEST, json.dumps(
            {"schemaVersion": 1, "fsLayers": []}).encode())
        try:
            with pytest.raises(ResolveError, match="cannot pull"):
                resolve_image(f"127.0.0.1:{reg.port}/org/app:1.0",
                              daemon=DaemonClient(sockets=()),
                              registry=RegistryClient())
        finally:
            reg.stop()

    def test_blob_digest_verified(self):
        reg = FakeRegistry().start()
        # corrupt one blob so its content no longer matches its digest
        k = next(iter(reg.blobs))
        reg.blobs[k] = reg.blobs[k] + b"tamper"
        try:
            with pytest.raises(RegistryError, match="digest"):
                DistributionClient().pull(
                    f"127.0.0.1:{reg.port}/org/app:1.0")
        finally:
            reg.stop()


class TestAdvisorRound4:
    def test_pinned_manifest_digest_verified(self):
        """A manifest fetched by @sha256: digest must hash to that
        digest before any blob digests inside it are trusted
        (advisor r4: go-containerregistry validates this)."""
        reg = FakeRegistry().start()
        bogus = "sha256:" + "b" * 64
        # registry serves SOME valid manifest under a digest key it
        # does not actually hash to
        reg.manifests[bogus] = reg.manifests["1.0"]
        try:
            with pytest.raises(RegistryError,
                               match="manifest digest mismatch"):
                DistributionClient().pull(
                    f"127.0.0.1:{reg.port}/org/app@{bogus}")
        finally:
            reg.stop()

    def test_pinned_manifest_digest_match_ok(self):
        reg = FakeRegistry().start()
        mdigest = next(k for k in reg.manifests
                       if k.startswith("sha256:"))
        try:
            src = DistributionClient().pull(
                f"127.0.0.1:{reg.port}/org/app@{mdigest}")
            assert "lib/apk/db/installed" in _scan_src(src)
            src.cleanup()
        finally:
            reg.stop()

    def test_platform_selected_manifest_digest_verified(self):
        """The image manifest resolved FROM a manifest list is also
        digest-pinned; tampering with it must be caught."""
        reg = FakeRegistry().start()
        mdigest = next(k for k in reg.manifests
                       if k.startswith("sha256:"))
        ctype, body = reg.manifests[mdigest]
        reg.manifests[mdigest] = (ctype, body + b" ")
        try:
            with pytest.raises(RegistryError,
                               match="manifest digest mismatch"):
                DistributionClient(platform="linux/amd64").pull(
                    f"127.0.0.1:{reg.port}/org/app:multi")
        finally:
            reg.stop()
