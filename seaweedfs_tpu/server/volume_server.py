"""Volume server: HTTP blob data path + admin/EC control plane.

Blob API matches the reference volume server HTTP surface
(weed/server/volume_server_handlers_write.go, _read.go):
  POST/PUT /{fid}   upload (raw body or multipart), ?type=replicate marks a
                    forwarded replica write (no re-fan-out)
  GET /{fid}        read (EC volumes served transparently, degraded reads
                    reconstruct online — volume_server_handlers_read.go:67)
  DELETE /{fid}     delete (+replica fan-out)

Admin endpoints carry what the reference does over ~45 gRPC RPCs
(volume_grpc_erasure_coding.go and friends): allocate/delete volumes,
vacuum, EC generate/mount/unmount/copy/rebuild/read/to-volume, file pull.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time

import aiohttp
from aiohttp import web

from seaweedfs_tpu.security import jwt as sjwt
from seaweedfs_tpu.stats import (heat, metrics, netflow, pipeline,
                                  profile, trace)
from seaweedfs_tpu.utils import resilience
from seaweedfs_tpu.utils.http import aiohttp_trace_config
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.ec import ec_files, ec_volume as ecv, layout
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.security.tls import scheme as _tls_scheme
from seaweedfs_tpu.security import tls as _tls

log = logging.getLogger("volume")

EC_FILE_EXTS = [layout.to_ext(i)
                for i in range(layout.MAX_TOTAL_SHARDS)] + \
    [".ecx", ".ecj", ".vif"]


def _topo_locality_name(cls: int) -> str:
    from seaweedfs_tpu.topology.topology import locality_name
    return locality_name(cls)

try:
    from aiohttp.http_writer import StreamWriter as _AioSW
    from aiohttp.http_writer import _serialize_headers as _ser_headers
    # write_eof leans on these writer privates too — probe them all, so a
    # partial aiohttp internals change disables the fast path instead of
    # 500ing the hottest GET route
    if not all(hasattr(_AioSW, a)
               for a in ("_writelines", "_write", "chunked")):
        _ser_headers = None
except ImportError:  # aiohttp internals moved: fall back to two writes
    _ser_headers = None


class _OneShotResponse(web.Response):
    """web.Response that defers the header write and flushes headers+body
    in ONE transport write.  Stock aiohttp issues two socket sends per
    response (headers at prepare, body at write_eof); on syscall-taxed
    hosts that second send is a measurable slice of a small-blob GET, and
    the blob read path is exactly small responses at high rate.  Any
    non-simple shape (chunked, compressed, payload body, empty-body
    methods) falls back to the stock path."""

    async def _write_headers(self) -> None:
        if _ser_headers is None:
            return await super()._write_headers()
        version = self._req.version
        status_line = (f"HTTP/{version[0]}.{version[1]} "
                       f"{self._status} {self._reason}")
        self._hdr_buf = _ser_headers(status_line, self._headers)

    async def write_eof(self, data: bytes = b"") -> None:
        buf = getattr(self, "_hdr_buf", None)
        if buf is None:
            return await super().write_eof(data)
        self._hdr_buf = None
        writer = self._payload_writer
        try:
            # everything read here is aiohttp-private; an internals
            # change must degrade to the stock two-write path, not 500
            # the hottest GET route (no bytes are on the wire yet)
            from aiohttp.payload import Payload
            body = (self._body if self._compressed_body is None
                    else self._compressed_body)
            simple = (writer is not None and not self._eof_sent
                      and not writer.chunked and writer._compress is None
                      and not self._must_be_empty_body
                      and not isinstance(body, Payload) and not data)
        except AttributeError:
            simple = False
        if not simple:
            if writer is not None and not self._eof_sent:
                writer._write(buf)
            return await super().write_eof(data)
        if body:
            if writer.length is not None:
                writer.length = max(0, writer.length - len(body))
            writer._writelines((buf, body))
        else:
            writer._write(buf)
        await web.StreamResponse.write_eof(self)


class VolumeServer:
    def __init__(self, directories: list[str], master_url: str,
                 host: str = "127.0.0.1", port: int = 8080,
                 public_url: str = "", max_volumes: int = 8,
                 data_center: str = "", rack: str = "",
                 heartbeat_interval: float = 3.0, security=None,
                 concurrent_uploads: int = 64,
                 concurrent_downloads: int = 256):
        self.security = security
        self.host, self.port = host, port
        self.url = f"{host}:{port}"
        self.public_url = public_url or self.url
        # comma-separated master list (HA): heartbeats follow the leader
        self.master_urls = [m.strip() for m in master_url.split(",")
                            if m.strip()]
        self.master_url = self.master_urls[0]
        self.data_center, self.rack = data_center, rack
        self.heartbeat_interval = heartbeat_interval
        self.store = Store(directories, max_volumes, self.public_url)
        self.volume_size_limit = 30 * 1024 * 1024 * 1024

        self.app = web.Application(
            client_max_size=256 * 1024 * 1024,
            middlewares=[trace.aiohttp_middleware("volume")])
        netflow.install(self.app, "volume")
        self.app.add_routes(trace.debug_routes())
        self.app.add_routes([
            web.get("/", self.handle_ui),
            web.get("/status", self.handle_status),
            web.get("/metrics", self.handle_metrics),
            web.get("/heat", heat.handle_heat),
            web.get("/perf", pipeline.handle_perf),
            web.post("/admin/assign_volume", self.handle_assign_volume),
            web.post("/admin/volume/delete", self.handle_volume_delete),
            web.post("/admin/leave", self.handle_leave),
            web.post("/admin/volume/readonly", self.handle_volume_readonly),
            web.post("/admin/volume/configure_replication",
                     self.handle_configure_replication),
            web.post("/admin/volume/mount", self.handle_volume_mount),
            web.post("/admin/volume/unmount", self.handle_volume_unmount),
            web.post("/admin/volume/vacuum", self.handle_vacuum),
            web.post("/admin/volume/copy", self.handle_volume_copy),
            web.post("/admin/volume/move", self.handle_volume_move),
            web.post("/admin/volume/unconvert",
                     self.handle_volume_unconvert),
            web.post("/admin/volume/tier_move", self.handle_tier_move),
            web.post("/admin/volume/tier_download",
                     self.handle_tier_download),
            web.get("/admin/volume/needles", self.handle_volume_needles),
            web.post("/admin/ec/generate", self.handle_ec_generate),
            web.post("/admin/ec/fleet_convert",
                     self.handle_ec_fleet_convert),
            web.get("/admin/ec/progress", self.handle_ec_progress),
            web.post("/admin/ec/cancel", self.handle_ec_cancel),
            web.post("/admin/ec/rebuild", self.handle_ec_rebuild),
            web.post("/admin/ec/mount", self.handle_ec_mount),
            web.post("/admin/ec/unmount", self.handle_ec_unmount),
            web.post("/admin/ec/delete_shards", self.handle_ec_delete_shards),
            web.post("/admin/ec/copy", self.handle_ec_copy),
            web.post("/admin/ec/to_volume", self.handle_ec_to_volume),
            web.post("/admin/ec/recode", self.handle_ec_recode),
            web.get("/admin/ec/shard_read", self.handle_ec_shard_read),
            web.post("/admin/ec/partial", self.handle_ec_partial),
            web.get("/admin/ec/probe_read", self.handle_ec_probe_read),
            web.get("/admin/file", self.handle_file_pull),
            web.post("/admin/query", self.handle_query),
            web.post("/admin/scrub", self.handle_scrub),
            web.post("/admin/scrub_rate", self.handle_scrub_rate),
            web.post("/admin/faults", self.handle_faults),
            web.route("*", "/{fid:[^/]*,[^/]+}", self.handle_blob),
        ])
        # in-flight throttling (reference: volume server
        # -concurrentUploadLimitMB / inFlightUploadDataLimitCond)
        self._upload_sem = asyncio.Semaphore(concurrent_uploads)
        # vid -> live EC-generate job state (observable + cancellable; the
        # reference streams this over its gRPC seam)
        self._ec_jobs: dict[int, dict] = {}
        self._download_sem = asyncio.Semaphore(concurrent_downloads)
        self._runner: web.AppRunner | None = None
        self._session: aiohttp.ClientSession | None = None
        self._hb_task: asyncio.Task | None = None
        self._wire_pb: bool | None = None  # protobuf heartbeat framing
        # vid -> (expiry, shard location map) for degraded-read fan-out;
        # accessed from shard_reader worker threads, hence the locks.
        # The master fetch itself runs under a PER-VID lock so a stalled
        # lookup for one volume can't serialize degraded reads (or even
        # cache hits) on every other volume behind a 10s master timeout;
        # _ec_loc_lock only guards the cache/lock-table dicts.
        self._ec_loc_cache: dict[int, tuple[float, dict]] = {}
        import threading as _threading
        self._ec_loc_lock = _threading.Lock()
        self._ec_loc_vid_locks: dict[int, _threading.Lock] = {}
        # self-healing plane: background scrubber (maintenance/scrub.py)
        # + injected-fault state (maintenance/faults.py, test-only)
        self.scrubber = None
        self._fault_delay_shard_read = 0.0
        self._fault_delay_file_pull = 0.0
        # vids with an /admin/volume/move in flight FROM this server: a
        # second concurrent move of the same volume would stage copies
        # on two targets and commit both — two live copies of a
        # single-replica volume silently diverge
        self._moves_active: set[int] = set()

    # -- lifecycle -----------------------------------------------------

    async def start(self) -> None:
        # build/load the protobuf wire module off the event loop: first
        # use can shell out to protoc, which must not stall live requests
        from seaweedfs_tpu import pb
        await asyncio.to_thread(pb.available)
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(ssl=_tls.client_ssl()),
            timeout=aiohttp.ClientTimeout(total=300),
            trace_configs=[aiohttp_trace_config("volume")])
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port,
                           ssl_context=_tls.server_ssl("volume"))
        await site.start()
        try:
            await self._heartbeat_once()
        except aiohttp.ClientError as e:
            # master not up yet; the heartbeat loop keeps retrying (and
            # rotates through -mserver candidates under HA)
            log.warning("initial heartbeat failed: %s", e)
            if len(self.master_urls) > 1:
                i = self.master_urls.index(self.master_url)
                self.master_url = self.master_urls[
                    (i + 1) % len(self.master_urls)]
        self._hb_task = asyncio.create_task(self._heartbeat_loop())
        profile.ensure_started()  # WEEDTPU_PROFILE_HZ, process-wide
        # test-only fault plan from the environment (maintenance/faults.py)
        from seaweedfs_tpu.maintenance import faults as _faults
        _faults.register_node(self.url, "volume")
        for f in _faults.parse_env(os.environ.get("WEEDTPU_FAULTS", "")):
            if f["action"] == "delay_shard_read":
                self._fault_delay_shard_read = f["ms"] / 1000.0
            elif f["action"] == "delay_file_pull":
                self._fault_delay_file_pull = f["ms"] / 1000.0
            else:
                try:
                    _faults.apply(self.store, f)
                except Exception as e:
                    log.warning("env fault %s failed: %s", f, e)
        # background scrubber: WEEDTPU_SCRUB_MBPS=0 disables
        try:
            mbps = float(os.environ.get("WEEDTPU_SCRUB_MBPS", "8"))
        except ValueError:
            mbps = 8.0
        if mbps > 0:
            from seaweedfs_tpu.maintenance.scrub import Scrubber
            self.scrubber = Scrubber(
                self.store, mbps=mbps, report=self._report_scrub,
                shard_reader_factory=self._shard_reader).start()
        log.info("volume server on %s (dirs=%s)", self.url,
                 [l.directory for l in self.store.locations])

    async def stop(self) -> None:
        if self.scrubber is not None:
            await asyncio.to_thread(self.scrubber.stop)
        if self._hb_task:
            self._hb_task.cancel()
        if self._session:
            await self._session.close()
        if self._runner:
            await self._runner.cleanup()
        self.store.close()
        # retire this instance's capacity series: heartbeats stamped
        # per-dir/per-volume gauges into the process-global registry,
        # and a restarted/decommissioned server must not leave them
        # behind as stale series
        metrics.DISK_BYTES.remove_matching(vs=self.url)
        metrics.VOLUME_SIZE.remove_matching(vs=self.url)

    async def _heartbeat_loop(self) -> None:
        while True:
            await asyncio.sleep(self.heartbeat_interval)
            try:
                await self._heartbeat_once()
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                log.warning("heartbeat to master %s failed: %s",
                            self.master_url, e)
                # dead leader: rotate through the configured master list so
                # a raft failover picks up (reference: volume servers dial
                # every master until they find the leader)
                if len(self.master_urls) > 1:
                    i = self.master_urls.index(self.master_url) \
                        if self.master_url in self.master_urls else -1
                    self.master_url = self.master_urls[
                        (i + 1) % len(self.master_urls)]

    async def _heartbeat_once(self) -> None:
        if getattr(self, "_left", False):
            # decommissioned via /admin/leave: stray admin calls that
            # trigger delta beats must not silently re-register us
            return
        beat = self.store.collect_heartbeat()
        metrics.VOLUME_COUNT_GAUGE.labels("", "normal").set(
            len(beat.get("volumes", [])))
        metrics.VOLUME_COUNT_GAUGE.labels("", "ec").set(
            len(beat.get("ec_shards", [])))
        # capacity inputs for the master's history plane: per-data-dir
        # disk occupancy + per-volume sizes, refreshed at heartbeat
        # cadence so the fill-rate regression (stats/history.py
        # CapacityForecaster) has a live series to fit
        for loc in self.store.locations:
            try:
                st = os.statvfs(loc.directory)
            except OSError:
                continue
            total = float(st.f_frsize * st.f_blocks)
            free = float(st.f_frsize * st.f_bavail)
            for kind, v in (("total", total), ("used", total - free),
                            ("free", free)):
                metrics.DISK_BYTES.labels(self.url, loc.directory,
                                          kind).set(v)
        for v in beat.get("volumes", []):
            # the vs label keeps replicas apart: the history store sums
            # same-labeled gauges across nodes, and a replicated volume
            # must not forecast at 2x its real size
            metrics.VOLUME_SIZE.labels(str(v["id"]), self.url).set(
                v["size"])
        beat.update({"id": self.url, "url": self.url,
                     "public_url": self.public_url,
                     "data_center": self.data_center, "rack": self.rack})
        # binary protobuf framing when the wire layer is built (reference:
        # master.proto Heartbeat); JSON otherwise or when forced.  A 415
        # from a JSON-only master latches the fallback.  Only the REQUEST
        # framing differs — response handling (size limit, 409
        # leader-follow, rotation) is shared so the two wires cannot
        # diverge.
        from seaweedfs_tpu import pb
        use_pb = self._wire_pb
        if use_pb is None:
            use_pb = self._wire_pb = (
                os.environ.get("WEEDTPU_WIRE", "pb") != "json"
                and pb.available())
        url = f"{_tls_scheme()}://{self.master_url}/heartbeat"
        if use_pb:
            req = self._session.post(
                url, data=pb.heartbeat_to_bytes(beat),
                headers={"Content-Type": pb.CONTENT_TYPE})
        else:
            req = self._session.post(url, json=beat)
        async with req as r:
            if r.status == 415 and use_pb:
                self._wire_pb = False
                return await self._heartbeat_once()
            if r.status == 200:
                data = await r.json()
                self.volume_size_limit = data.get(
                    "volume_size_limit", self.volume_size_limit)
                return
            if r.status == 409:
                # raft follower: re-point at the leader it names, else
                # rotate through the configured master list
                data = await r.json()
                leader = data.get("leader")
                if leader and leader != self.master_url:
                    log.info("heartbeat: switching master %s -> leader %s",
                             self.master_url, leader)
                    self.master_url = leader
                elif self.master_urls:
                    i = self.master_urls.index(self.master_url) \
                        if self.master_url in self.master_urls else -1
                    self.master_url = self.master_urls[
                        (i + 1) % len(self.master_urls)]

    # -- blob data path -------------------------------------------------

    async def handle_blob(self, req: web.Request) -> web.StreamResponse:
        try:
            fid = t.FileId.parse(req.match_info["fid"])
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        if req.method in ("POST", "PUT", "DELETE"):
            # write JWT check (reference: volume_server_handlers_write.go:33)
            err = self._check_jwt(req)
            if err is not None:
                return err
        if req.method in ("POST", "PUT"):
            metrics.VOLUME_REQUEST_COUNTER.labels("write").inc()
            async with self._upload_sem:
                with metrics.VOLUME_REQUEST_HISTOGRAM.labels("write").time():
                    return await self._write_blob(req, fid)
        if req.method == "GET" or req.method == "HEAD":
            # read JWT, only when a [jwt.signing.read] key is configured
            if self.security is not None and self.security.volume_read:
                token = sjwt.token_from_request(req.headers, req.query)
                try:
                    sjwt.decode_jwt(self.security.volume_read, token,
                                    expected_fid=req.match_info["fid"])
                except sjwt.JwtError as e:
                    return web.json_response({"error": str(e)}, status=401)
            metrics.VOLUME_REQUEST_COUNTER.labels("read").inc()
            async with self._download_sem:
                with metrics.VOLUME_REQUEST_HISTOGRAM.labels("read").time():
                    return await self._read_blob(req, fid)
        if req.method == "DELETE":
            metrics.VOLUME_REQUEST_COUNTER.labels("delete").inc()
            return await self._delete_blob(req, fid)
        return web.json_response({"error": "method not allowed"}, status=405)

    def _check_jwt(self, req: web.Request) -> web.Response | None:
        if self.security is None or not self.security.volume_write:
            return None
        token = sjwt.token_from_request(req.headers, req.query)
        if not token:
            return web.json_response({"error": "missing jwt"}, status=401)
        try:
            sjwt.decode_jwt(self.security.volume_write, token,
                            expected_fid=req.match_info["fid"])
        except sjwt.JwtError as e:
            return web.json_response({"error": str(e)}, status=401)
        return None

    async def _write_blob(self, req: web.Request, fid: t.FileId) -> web.Response:
        name, mime, data = b"", b"", b""
        ctype = req.headers.get("Content-Type", "")
        if ctype.startswith("multipart/"):
            reader = await req.multipart()
            part = await reader.next()
            while part is not None:
                if part.name in (None, "file"):
                    name = (part.filename or "").encode()
                    pm = part.headers.get("Content-Type", "")
                    mime = b"" if pm == "application/octet-stream" else pm.encode()
                    data = await part.read(decode=False)
                    break
                part = await reader.next()
        else:
            data = await req.read()
            if ctype and ctype != "application/octet-stream":
                mime = ctype.encode()
            hname = req.headers.get("X-File-Name")
            if hname:
                name = hname.encode()
        n = ndl.Needle(cookie=fid.cookie, id=fid.key, data=data,
                       name=name, mime=mime,
                       last_modified=int(time.time()))
        try:
            size = await asyncio.to_thread(
                self.store.write_needle, fid.volume_id, n)
        except KeyError:
            return web.json_response({"error": "volume not found"}, status=404)
        except PermissionError as e:
            return web.json_response({"error": str(e)}, status=409)
        del size
        if heat.ambient_is_data():
            # workload heat: replica fan-in (class=replication) and
            # canary sentinels (internal) stay out of the sketches
            heat.record("volume", str(fid.volume_id), len(data), "write")

        if req.query.get("type") != "replicate":
            err = await self._replicate(fid, "PUT", data, name, mime)
            if err:
                return web.json_response({"error": err}, status=500)
        return web.json_response({"name": name.decode(errors="replace"),
                                  "size": len(data), "eTag": f"{n.checksum:x}"},
                                 status=201)

    async def _replicate(self, fid: t.FileId, method: str,
                         data: bytes | None, name: bytes = b"",
                         mime: bytes = b"") -> str | None:
        """Synchronous fan-out to the other replica locations
        (reference: weed/topology/store_replicate.go:24-135).  All peers
        are written CONCURRENTLY — the caller still waits for every ack
        (same strict semantics), but the added latency is one peer
        round-trip, not the sum of them."""
        vol = self.store.get_volume(fid.volume_id)
        if vol is None or vol.super_block.replica_placement.copy_count <= 1:
            return None
        try:
            async with self._session.get(
                    f"{_tls_scheme()}://{self.master_url}/dir/lookup",
                    params={"volumeId": str(fid.volume_id)}) as r:
                locations = (await r.json()).get("locations", [])
        except aiohttp.ClientError as e:
            return f"replica lookup failed: {e}"
        peers = [l["url"] for l in locations if l["url"] != self.url]
        if not peers:
            return None
        headers = {}
        if self.security is not None and self.security.volume_write:
            headers["Authorization"] = "Bearer " + sjwt.gen_jwt(
                self.security.volume_write, str(fid))
        if mime:
            headers["Content-Type"] = mime.decode(errors="replace")
        if name:
            headers["X-File-Name"] = name.decode(errors="replace")

        async def one(peer: str) -> str | None:
            url = f"{_tls_scheme()}://{peer}/{fid}?type=replicate"
            try:
                with trace.span("volume.replicate_peer", peer=peer,
                                method=method):
                    if method == "PUT":
                        async with self._session.put(url, data=data,
                                                     headers=headers) as r:
                            if r.status >= 300:
                                return f"replica write to {peer}: {r.status}"
                    else:
                        async with self._session.delete(url,
                                                        headers=headers) as r:
                            if r.status >= 300:
                                return \
                                    f"replica delete to {peer}: {r.status}"
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                return f"replica {method} to {peer} failed: {e!r}"
            return None

        # return_exceptions so one unexpected failure cannot abandon the
        # sibling writes as detached tasks that land AFTER the error is
        # reported — every peer's outcome is awaited and folded in.
        # Replica fan-out bytes are class=replication in the ledger; the
        # contextvar set here rides into the gathered tasks' contexts.
        with netflow.flow("replication"), \
                trace.span("volume.replicate", peers=len(peers),
                           method=method):
            results = await asyncio.gather(*(one(p) for p in peers),
                                           return_exceptions=True)
        for err in results:
            if isinstance(err, BaseException):
                return f"replica {method} failed: {err!r}"
            if err:
                return err
        return None

    PAGED_READ_MIN = 256 * 1024  # Range on bigger needles skips full load
    # small plain-volume needles are pread directly on the event loop:
    # cheaper than a thread-pool round-trip per request WHEN the pages are
    # cache-resident (the hot-blob case this server optimizes for).  The
    # tradeoff is deliberate: a cold page stalls the loop for one disk
    # read (~ms), so deployments whose working set exceeds RAM — where
    # most reads fault — should set WEEDTPU_INLINE_READ_MAX=0 to force
    # every read through the pool
    INLINE_READ_MAX = int(os.environ.get("WEEDTPU_INLINE_READ_MAX",
                                         str(64 * 1024)))

    async def _read_blob(self, req: web.Request, fid: t.FileId) -> web.StreamResponse:
        # parsing an EMPTY query string still costs a parse_qsl pass per
        # GET; the common blob read has no query at all
        query = req.query if req.query_string else {}
        rng0 = req.headers.get("Range", "")
        if rng0.startswith("bytes=") and "width" not in query \
                and "height" not in query:
            resp = await self._read_blob_paged(req, fid, rng0)
            if resp is not None:
                return resp
        try:
            n = self.store.read_needle_inline(
                fid.volume_id, fid.key, fid.cookie, self.INLINE_READ_MAX) \
                if self.INLINE_READ_MAX else None
            if n is None:
                n = await asyncio.to_thread(
                    self.store.read_needle, fid.volume_id, fid.key,
                    fid.cookie, self._shard_reader(fid.volume_id))
        except KeyError:
            return web.json_response({"error": "not found"}, status=404)
        except PermissionError:
            return web.json_response({"error": "cookie mismatch"}, status=404)
        except ValueError as e:
            # needle CRC mismatch / corrupt record: never return the bad
            # bytes — count it, log with the trace id, and serve from a
            # replica when one exists (maintenance satellite; the scrubber
            # finds these offline, this is the online backstop)
            return await self._blob_corrupt_fallback(req, fid, e)
        except IOError as e:
            return web.json_response({"error": str(e)}, status=500)
        if heat.ambient_is_data():
            heat.record("volume", str(fid.volume_id), len(n.data), "read")
        headers = {"Etag": f'"{n.checksum:x}"', "Accept-Ranges": "bytes"}
        if n.name:
            headers["Content-Disposition"] = \
                f'inline; filename="{n.name.decode(errors="replace")}"'
        data, status = n.data, 200
        # on-read image resize/crop (reference: images/resizing.go served
        # via ?width= on the volume read handler, needle.go:101-106)
        mime = n.mime.decode() if n.mime else ""
        if ("width" in query or "height" in query):
            from seaweedfs_tpu import images
            try:
                w = int(query.get("width", "0") or 0)
                h = int(query.get("height", "0") or 0)
            except ValueError:
                w = h = 0  # malformed size params are ignored
            if (w or h) and images.is_image_mime(mime):
                data = await asyncio.to_thread(
                    images.resized, data, mime, w, h,
                    query.get("mode", ""))
        rng = req.headers.get("Range", "")
        if rng.startswith("bytes=") and data:
            from seaweedfs_tpu.utils.http import parse_range
            try:
                lo, length = parse_range(rng, len(data))
            except ValueError:
                return web.Response(
                    status=416,
                    headers={"Content-Range": f"bytes */{len(data)}"})
            headers["Content-Range"] = \
                f"bytes {lo}-{lo + length - 1}/{len(data)}"
            data, status = data[lo:lo + length], 206
        body = b"" if req.method == "HEAD" else data
        return _OneShotResponse(
            body=body, status=status,
            content_type=(n.mime.decode() if n.mime else "application/octet-stream"),
            headers=headers)

    async def _read_blob_paged(self, req: web.Request, fid: t.FileId,
                               rng: str) -> web.StreamResponse | None:
        """Serve a Range request by reading only the needed page of a large
        plain-volume needle (reference: needle_read_page.go).  Returns None
        to fall back to the whole-record path (EC volumes, small needles,
        parse errors)."""
        v = self.store.get_volume(fid.volume_id)
        if v is None or v.version == t.VERSION1:
            return None  # EC/missing/V1: the whole-record path handles them
        loc = v.nm.get(fid.key)
        if loc is None or loc[1] < self.PAGED_READ_MIN:
            return None
        from seaweedfs_tpu.utils.http import parse_range
        try:
            # cheap probe: header + meta tail (cookie + TTL enforced, mime
            # and checksum recovered without touching the data bytes)
            meta = await asyncio.to_thread(
                v.read_needle_meta, fid.key, fid.cookie)
        except (KeyError, PermissionError):
            return web.json_response({"error": "not found"}, status=404)
        except (ValueError, EOFError, OSError):
            return None  # odd record: fall back to the full path
        total = meta.size
        if total < self.PAGED_READ_MIN:
            return None
        try:
            lo, length = parse_range(rng, total)
        except ValueError:
            return web.Response(
                status=416, headers={"Content-Range": f"bytes */{total}"})
        try:
            data = await asyncio.to_thread(
                v.read_needle_page, fid.key, lo, length, fid.cookie)
        except (KeyError, PermissionError):
            return web.json_response({"error": "not found"}, status=404)
        except (ValueError, EOFError, OSError):
            return None
        if heat.ambient_is_data():
            heat.record("volume", str(fid.volume_id), len(data), "read")
        headers = {"Accept-Ranges": "bytes",
                   "Etag": f'"{meta.checksum:x}"',
                   "Content-Range":
                   f"bytes {lo}-{lo + len(data) - 1}/{total}"}
        if meta.name:
            headers["Content-Disposition"] = \
                f'inline; filename="{meta.name.decode(errors="replace")}"'
        return web.Response(
            body=data, status=206,
            content_type=(meta.mime.decode() if meta.mime
                          else "application/octet-stream"),
            headers=headers)

    async def _blob_corrupt_fallback(self, req: web.Request, fid: t.FileId,
                                     err: Exception) -> web.StreamResponse:
        """A read hit corrupt bytes (CRC mismatch / unparseable record):
        count it, log an always-on line carrying the trace id, and proxy
        the read to another replica.  The peer is told not to fall back
        again (X-Weedtpu-No-Fallback) so two corrupt replicas cannot
        bounce a request between themselves."""
        from seaweedfs_tpu.utils import weedlog
        metrics.NEEDLE_CRC_MISMATCH.labels().inc()
        tctx = trace.current()
        # rate-limited per volume: a single hot corrupt chunk read
        # thousands of times a second must not storm the log (the
        # counter above still counts every one)
        weedlog.warn_ratelimited(
            f"crc_fallback:{fid.volume_id}", 5.0,
            "needle %s CRC mismatch on %s (trace %s): %s; trying replica",
            str(fid), self.url, tctx.trace_id if tctx else "-", err,
            name="volume")
        if req.headers.get("X-Weedtpu-No-Fallback"):
            return web.json_response({"error": str(err)}, status=500)
        locations: list[dict] = []
        try:
            async with self._session.get(
                    f"{_tls_scheme()}://{self.master_url}/dir/lookup",
                    params={"volumeId": str(fid.volume_id)}) as r:
                if r.status == 200:
                    locations = (await r.json()).get("locations", [])
        except (aiohttp.ClientError, asyncio.TimeoutError):
            pass
        for loc in locations:
            if loc["url"] == self.url:
                continue
            try:
                fwd = {"X-Weedtpu-No-Fallback": "1"}
                if req.headers.get("Range"):
                    fwd["Range"] = req.headers["Range"]
                with trace.span("volume.crc_fallback", peer=loc["url"]):
                    async with self._session.get(
                            f"{_tls_scheme()}://{loc['url']}/{fid}",
                            headers=fwd) as r:
                        if r.status not in (200, 206):
                            continue
                        body = await r.read()
                        headers = {"Accept-Ranges": "bytes"}
                        for h in ("Etag", "Content-Range",
                                  "Content-Disposition"):
                            if r.headers.get(h):
                                headers[h] = r.headers[h]
                        return web.Response(
                            body=b"" if req.method == "HEAD" else body,
                            status=r.status,
                            content_type=r.headers.get(
                                "Content-Type", "application/octet-stream"),
                            headers=headers)
            except (aiohttp.ClientError, asyncio.TimeoutError):
                continue
        return web.json_response({"error": str(err)}, status=500)

    async def _delete_blob(self, req: web.Request, fid: t.FileId) -> web.Response:
        try:
            size = await asyncio.to_thread(
                self.store.delete_needle, fid.volume_id, fid.key, fid.cookie)
        except KeyError:
            return web.json_response({"error": "not found"}, status=404)
        except PermissionError:
            return web.json_response({"error": "cookie mismatch"}, status=404)
        if req.query.get("type") != "replicate":
            err = await self._replicate(fid, "DELETE", None)
            if err:
                return web.json_response({"error": err}, status=500)
        return web.json_response({"size": size})

    def _ec_loc_vid_lock(self, vid: int):
        """Per-vid fetch lock, created on first use.  The table is pruned
        alongside the cache; a pruned-then-recreated lock merely allows
        two concurrent fetches for the same vid, resolved by the
        double-checked cache insert."""
        with self._ec_loc_lock:
            lk = self._ec_loc_vid_locks.get(vid)
            if lk is None:
                import threading as _threading
                lk = self._ec_loc_vid_locks[vid] = _threading.Lock()
            return lk

    def _ec_shard_locations(self, vid: int) -> dict:
        """Master shard-location lookup with a short TTL cache (reference:
        store_ec.go cachedLookupEcShardLocations and its TTL tiers) — a
        degraded read fans out to many shards and must not re-query the
        master once per shard.  The fetch runs under a per-vid lock, so a
        cold parallel fan-out issues ONE lookup per volume while lookups
        (and cache hits) for OTHER volumes proceed concurrently; empty
        results get a much shorter TTL (the reference's empty-list tier)
        so a transient bad answer can't blank a volume for 10s."""
        import urllib.request
        import json as _json
        with self._ec_loc_vid_lock(vid):
            now = time.monotonic()
            with self._ec_loc_lock:
                cached = self._ec_loc_cache.get(vid)
            if cached and cached[0] > now:
                return cached[1]
            try:
                with urllib.request.urlopen(
                        f"{_tls_scheme()}://{self.master_url}"
                        f"/dir/ec/lookup?volumeId={vid}",
                        timeout=10) as r:
                    shards = _json.load(r).get("shards", {})
            except Exception:
                # record a short-TTL negative entry before re-raising:
                # without a cache entry the vid's lock-table slot is never
                # eligible for eviction, and vid is client-controlled —
                # probing many vids against a dead master would grow
                # _ec_loc_vid_locks without bound
                with self._ec_loc_lock:
                    self._ec_loc_cache.setdefault(vid, (now + 1.0, {}))
                    self._ec_loc_evict_locked()
                raise
            # nearest-first candidate order (the planner's locality
            # ranking): degraded reads and survivor gathering try
            # same-rack peers before crossing racks/DCs
            for locs in shards.values():
                locs.sort(key=self._loc_rank)
            ttl = 10.0 if shards else 1.0
            with self._ec_loc_lock:
                self._ec_loc_cache[vid] = (now + ttl, shards)
                self._ec_loc_evict_locked()
            return shards

    def _loc_rank(self, loc) -> int:
        """Locality class of a shard-location record relative to this
        server (0 self, 1 same rack, 2 same DC, 3 remote DC).  Accepts a
        bare url string (older/minimal masters) as label-less."""
        if not isinstance(loc, dict):
            loc = {"url": loc}
        from seaweedfs_tpu.topology.topology import locality_class
        return locality_class(self.data_center, self.rack,
                              loc.get("dc", ""), loc.get("rack", ""),
                              same_node=loc.get("url") == self.url)

    def _ec_loc_evict_locked(self) -> None:
        """Bound the location cache AND its lock table (insertion order ==
        eviction order).  Caller holds _ec_loc_lock."""
        while len(self._ec_loc_cache) > 256:
            evicted = next(iter(self._ec_loc_cache))
            self._ec_loc_cache.pop(evicted)
            self._ec_loc_vid_locks.pop(evicted, None)

    def _shard_reader(self, vid: int):
        """Remote-shard fetch for EC degraded reads: ask the master where
        each shard lives, pull the byte range from a peer
        (reference: store_ec.go readRemoteEcShardInterval).  The trace
        context AND the ambient traffic class are captured HERE, on the
        calling thread, because read() runs on executor pool threads
        that never see the request's copied context — the captured Trace
        parents the per-fetch spans, and the class (data for a foreground
        degraded read, scrub when the scrubber asked, repair under the
        planner) rides X-Weedtpu-Class to the peer so both sides book
        the shard bytes under the same flow."""
        tctx = trace.current()
        flow_cls = netflow.current_class() or "data"
        # the ambient deadline is request-context state; capture it HERE
        # (the calling thread) so pool-thread fetches still honor it
        dl = resilience.deadline()

        def read(shard_id: int, offset: int, size: int) -> bytes | None:
            # runs inside a worker thread: use a blocking http client
            import urllib.request
            from seaweedfs_tpu.maintenance import faults as _faults
            try:
                shards = self._ec_shard_locations(vid)
                for loc in shards.get(str(shard_id), []):
                    if loc["url"] == self.url:
                        continue
                    # per-peer circuit breaker: a tripped peer is skipped
                    # outright — the next location (or reconstruction)
                    # serves the interval without paying its timeout
                    breaker = resilience.breaker_for(loc["url"]) \
                        if resilience.breaker_enabled() else None
                    if breaker is not None and not breaker.allow():
                        continue
                    try:
                        if _faults.NET_ACTIVE:
                            lat = _faults.check_net("volume", loc["url"])
                            if lat > 0:
                                time.sleep(lat)
                        # socket timeout respects the captured budget: a
                        # 200ms request must not park this thread for 30s
                        tmo = 30.0
                        if dl is not None:
                            tmo = min(tmo, dl - time.monotonic())
                            if tmo <= 0.01:
                                # budget spent: failing is OUR state,
                                # not the peer's — don't even dial (and
                                # never ding its breaker for it)
                                return None
                        with trace.span("volume.shard_fetch", parent=tctx,
                                        vid=vid, shard=shard_id,
                                        peer=loc["url"],
                                        bytes=size) as sp:
                            req = urllib.request.Request(
                                f"{_tls_scheme()}://{loc['url']}"
                                f"/admin/ec/shard_read?"
                                f"volume={vid}&shard={shard_id}"
                                f"&offset={offset}&size={size}")
                            # the peer's span must parent to THIS fetch
                            # span, not the request root, or the trace
                            # tree misattributes the peer's time
                            hdr_ctx = sp.trace or tctx
                            if hdr_ctx is not None:
                                req.add_header(
                                    trace.TRACE_HEADER,
                                    trace.format_header(hdr_ctx))
                            req.add_header(netflow.CLASS_HEADER, flow_cls)
                            req.add_header(netflow.ROLE_HEADER, "volume")
                            if dl is not None:
                                req.add_header(
                                    resilience.DEADLINE_HEADER,
                                    str(max(1, int((dl - time.monotonic())
                                                   * 1000))))
                            with urllib.request.urlopen(req,
                                                        timeout=tmo) as rr:
                                data = rr.read()
                            netflow.account("recv", flow_cls, "volume",
                                            len(data))
                            if len(data) != size:
                                sp.set(short=len(data))
                        if breaker is not None:
                            breaker.record(True)
                        if len(data) == size:
                            return data
                    except urllib.error.HTTPError:
                        # the peer ANSWERED (404 shard moved, 5xx): a
                        # routing/content miss, not a transport failure —
                        # breakers only count unreachable peers
                        if breaker is not None:
                            breaker.record(True)
                        continue
                    except OSError:
                        # a timeout caused by OUR nearly-spent budget is
                        # not evidence against the peer; real transport
                        # failures (and timeouts with budget to spare)
                        # are
                        if breaker is not None and \
                                (dl is None
                                 or dl - time.monotonic() > 0.05):
                            breaker.record(False)
                        continue
            except OSError:
                return None
            return None

        def locality_rank(shard_id: int) -> int:
            """Best locality class among a shard's remote locations —
            the EC read engine sorts survivor fan-outs with this so
            same-rack helpers are tried before cross-rack ones."""
            try:
                locs = self._ec_shard_locations(vid).get(str(shard_id), [])
            except Exception:
                return 3
            # _loc_rank accepts bare url strings (older/minimal
            # masters); mirror that here or the sort dies in its
            # advisory try/except and silently disables the ordering
            return min((self._loc_rank(l) for l in locs
                        if (l.get("url") if isinstance(l, dict) else l)
                        != self.url), default=3)

        read.locality_rank = locality_rank
        return read

    # -- admin: volumes --------------------------------------------------

    async def handle_ui(self, req: web.Request) -> web.Response:
        """Operator status page with volume and EC shard tables
        (reference: weed/server/volume_server_ui/templates.go)."""
        from seaweedfs_tpu.server import ui
        hb = self.store.collect_heartbeat()
        vol_rows = [[v["id"], v.get("collection", "") or "-",
                     ui.fmt_bytes(v.get("size", 0)), v.get("file_count", 0),
                     v.get("delete_count", 0),
                     ui.fmt_bytes(v.get("deleted_bytes", 0)),
                     v.get("replica_placement", "000"),
                     v.get("ttl", "") or "-", v.get("read_only", False)]
                    for v in sorted(hb.get("volumes", []),
                                    key=lambda v: v["id"])]
        ec_rows = [[e["id"], e.get("collection", "") or "-",
                    " ".join(str(s) for s in sorted(e.get("shards", []))),
                    len(e.get("shards", []))]
                   for e in sorted(hb.get("ec_shards", []),
                                   key=lambda e: e["id"])]
        return web.Response(text=ui.render(
            f"weedtpu volume server {self.url}",
            {"server": ui.Table(
                ["master", "max slots", "volumes", "ec volumes"],
                [[self.master_url, hb.get("max_volume_count", 0),
                  len(vol_rows), len(ec_rows)]]),
             "volumes": ui.Table(
                ["id", "collection", "size", "files", "deleted",
                 "deleted bytes", "replication", "ttl", "read-only"],
                vol_rows),
             "ec shards": ui.Table(
                ["volume", "collection", "shards here", "count"], ec_rows)},
            links={"metrics": "/metrics", "status json": "/status"}),
            content_type="text/html")

    async def handle_status(self, req: web.Request) -> web.Response:
        return web.json_response(self.store.collect_heartbeat())

    async def handle_metrics(self, req: web.Request) -> web.Response:
        # per-stage degraded-read counters live on each mounted EcVolume;
        # mirror their sums into the registry at scrape time
        totals: dict[str, int] = {}
        for loc in self.store.locations:
            for ev in list(loc.ec_volumes.values()):
                for stat, v in ev.read_stats_snapshot().items():
                    totals[stat] = totals.get(stat, 0) + v
        for stat, v in totals.items():
            metrics.EC_DEGRADED_READ.labels(stat).set(v)
        return metrics.scrape_response(req)

    async def handle_assign_volume(self, req: web.Request) -> web.Response:
        body = await req.json()
        try:
            self.store.allocate_volume(
                body["volume"], body.get("collection", ""),
                body.get("replication", "000"), body.get("ttl", ""))
        except FileExistsError:
            pass  # idempotent
        except OSError as e:
            return web.json_response({"error": str(e)}, status=500)
        return web.json_response({})

    async def handle_volume_delete(self, req: web.Request) -> web.Response:
        body = await req.json()
        self.store.delete_volume(body["volume"])
        await self._heartbeat_once()
        return web.json_response({})

    async def handle_leave(self, req: web.Request) -> web.Response:
        """Stop heartbeating so the master expires this server from the
        topology (reference: volume_grpc_admin.go VolumeServerLeave) —
        the clean-decommission step after volume.server.evacuate."""
        self._left = True  # sticky: delta beats from admin calls stay off
        if self._hb_task:
            self._hb_task.cancel()
            self._hb_task = None
        return web.json_response({"ok": True})

    async def handle_configure_replication(self, req: web.Request
                                           ) -> web.Response:
        """Rewrite the replica-placement byte in the super block
        (reference: volume_grpc_admin.go VolumeConfigure)."""
        body = await req.json()
        v = self.store.get_volume(body["volume"])
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        try:
            rp = t.ReplicaPlacement.parse(body.get("replication", "000"))
        except (ValueError, KeyError) as e:
            return web.json_response({"error": str(e)}, status=400)
        try:
            await asyncio.to_thread(v.set_replica_placement, rp)
        except PermissionError as e:
            return web.json_response({"error": str(e)}, status=409)
        await self._heartbeat_once()
        return web.json_response({"replication": str(rp)})

    async def handle_volume_unmount(self, req: web.Request) -> web.Response:
        """Close a volume without deleting its files (reference:
        VolumeUnmount, volume_grpc_admin.go) — frees the slot; a later
        mount or restart picks the files back up."""
        body = await req.json()
        vid = body["volume"]
        for loc in self.store.locations:
            v = loc.volumes.pop(vid, None)
            if v is not None:
                await asyncio.to_thread(v.close)
                await self._heartbeat_once()
                return web.json_response({})
        return web.json_response({"error": "volume not found"}, status=404)

    async def handle_volume_mount(self, req: web.Request) -> web.Response:
        """(Re)open an existing volume's files (reference: VolumeMount)."""
        body = await req.json()
        vid = body["volume"]
        collection = body.get("collection", "")
        if self.store.get_volume(vid) is not None:
            return web.json_response({})  # already mounted
        from seaweedfs_tpu.storage.volume import Volume
        for loc in self.store.locations:
            # an earlier unmount leaves the collection recorded; try it
            # first so `volume.mount -volumeId N` works without -collection
            collection = collection or loc.collections.get(vid, "")
            base = loc.base_path(vid, collection)
            if os.path.exists(base + ".dat") or \
                    os.path.exists(base + ".tier"):
                try:
                    vol = await asyncio.to_thread(
                        Volume, loc.directory, collection, vid)
                except Exception as e:
                    return web.json_response({"error": f"load: {e}"},
                                             status=500)
                loc.volumes[vid] = vol
                loc.collections[vid] = collection
                await self._heartbeat_once()
                return web.json_response({})
        return web.json_response({"error": "volume files not found"},
                                 status=404)

    async def handle_volume_readonly(self, req: web.Request) -> web.Response:
        body = await req.json()
        v = self.store.get_volume(body["volume"])
        if v is None:
            return web.json_response({"error": "volume not found"}, status=404)
        v.read_only = bool(body.get("readonly", True))
        await self._heartbeat_once()
        return web.json_response({})

    async def handle_vacuum(self, req: web.Request) -> web.Response:
        body = await req.json()
        v = self.store.get_volume(body["volume"])
        if v is None:
            return web.json_response({"error": "volume not found"}, status=404)
        garbage = v.garbage_ratio()
        await asyncio.to_thread(v.compact)
        return web.json_response({"garbage_ratio": garbage})

    # -- admin: EC -------------------------------------------------------

    def _ec_base(self, vid: int) -> str | None:
        for loc in self.store.locations:
            for cand in (loc.base_path(vid, loc.collections.get(vid, "")),
                         loc.base_path(vid)):
                if any(os.path.exists(cand + ext) for ext in
                       (".dat", ".ecx", layout.to_ext(0))):
                    return cand
        return None

    @staticmethod
    def _request_blocks(body: dict) -> tuple[int, int]:
        """(large, small) block sizes a conversion request asks the set to
        be cut with: its `large_block_bytes` / `small_block_bytes`, each
        upstream's constant where the body has none.  The pair is the
        volume's from then on (the `.vif` records it).  One the layout
        cannot be cut with raises ValueError: a 400, nothing written."""
        large = body.get("large_block_bytes", layout.LARGE_BLOCK_SIZE)
        small = body.get("small_block_bytes", layout.SMALL_BLOCK_SIZE)
        ec_files.check_blocks(large, small)
        return large, small

    async def handle_ec_generate(self, req: web.Request) -> web.Response:
        """VolumeEcShardsGenerate (volume_grpc_erasure_coding.go:38): .dat ->
        .ec00-13 + .ecx, parity computed by the TPU codec.  The body may
        say the block sizes the set is cut with (`large_block_bytes`,
        `small_block_bytes`; upstream's 1 GB / 1 MB else); the job's
        `stages` and the answer say them back."""
        body = await req.json()
        vid = body["volume"]
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"}, status=404)
        base = v._base
        from seaweedfs_tpu.ops import codecs as _codecs
        spec = _codecs.parse_tag(body.get("codec") or _codecs.default_tag())
        try:
            large, small = self._request_blocks(body)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        if self._ec_jobs.get(vid, {}).get("state") == "running":
            return web.json_response({"error": "encode already running"},
                                     status=409)
        # `stages` is written in-place by the encode pipeline (per-stage
        # seconds, mode, overlap_frac), so /admin/ec/progress shows WHERE
        # a long encode is spending its time, not just how far it is
        stages: dict = {}
        job = {"state": "running", "kind": "encode", "bytes_done": 0,
               "total": os.path.getsize(base + ".dat"),
               "cancel": False, "error": None, "started": time.time(),
               "stages": stages}
        self._ec_jobs[vid] = job
        job["codec"] = spec.tag

        def gen():
            v.nm.flush()
            ec_files.write_ec_files(
                base, large_block=large, small_block=small,
                progress=lambda n: job.__setitem__("bytes_done", n),
                cancel=lambda: job["cancel"],
                stats=stages, codec_tag=spec.tag)
            ec_files.write_sorted_ecx(base + ".idx")
            metrics.EC_ENCODE_BYTES.labels(
                stages.get("backend", "unknown")).inc(job["total"])

        try:
            await asyncio.to_thread(gen)
        except ec_files.EncodeCancelled:
            # write_ec_files builds under temp names: a cancelled encode
            # already cleaned up after itself and any previous valid shard
            # set is untouched
            job["state"] = "cancelled"
            return web.json_response({"error": "cancelled"}, status=409)
        except _codecs.CodecUnsupported as e:
            # refused by the codec resolution, before any .tmp file
            job["state"] = "failed"
            job["error"] = str(e)
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            job["state"] = "failed"
            job["error"] = str(e)
            raise
        job["state"] = "done"
        job["bytes_done"] = job["total"]
        return web.json_response({"shards": list(range(spec.n)),
                                  "codec": spec.tag,
                                  "large_block_bytes": large,
                                  "small_block_bytes": small})

    async def handle_ec_fleet_convert(self, req: web.Request
                                      ) -> web.Response:
        """Batched multi-volume EC conversion (ops/fleet_convert): the
        listed local volumes' units interleave into ONE device-resident
        encode stream instead of N serial /admin/ec/generate rounds.
        Driven by the master's conversion scheduler (maintenance/convert)
        as paced background work; every network hop made on its behalf
        books netflow class=convert.  Participating volumes are frozen
        read-only for the conversion (shell ec.encode's readonly step —
        a write landing after the .dat snapshot would be missing from
        the EC set); failure or cancel thaws them, success keeps the
        freeze.  Each volume registers under the shared per-vid job
        table, so /admin/ec/progress observes it and /admin/ec/cancel on
        ANY participating vid aborts the whole run (uncommitted volumes
        roll back to their previous state).  `large_block_bytes` /
        `small_block_bytes` in the body are the block sizes every listed
        volume is cut with, as /admin/ec/generate takes them."""
        body = await req.json()
        vids: list[int] = []
        for v_ in (body.get("volumes") or [])[:64]:  # bounded fan-in
            try:
                vid = int(v_)
            except (TypeError, ValueError):
                continue
            if vid not in vids:
                vids.append(vid)
        vols, skipped = [], {}
        for vid in vids:
            v = self.store.get_volume(vid)
            if v is None:
                skipped[str(vid)] = "not found"
            elif self._ec_jobs.get(vid, {}).get("state") == "running":
                skipped[str(vid)] = "ec job already running"
            else:
                vols.append((vid, v))
        if not vols:
            return web.json_response(
                {"error": "no convertible volumes here",
                 "skipped": skipped}, status=404)
        # the stream's codec, resolved before anything is frozen or
        # opened: a tag it does not carry is refused here
        from seaweedfs_tpu.ops import codecs as _codecs
        from seaweedfs_tpu.ops import fleet_convert as _fleet
        try:
            codec = await asyncio.to_thread(_fleet.fleet_codec, None,
                                            body.get("codec"))
            large, small = self._request_blocks(body)
        except (_codecs.CodecUnsupported, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        # freeze writes for the duration (the same contract as shell
        # ec.encode's readonly step): a needle appended after the .dat
        # snapshot would be silently absent from the committed EC set.
        # A failed/cancelled conversion thaws; success keeps the freeze —
        # the shard set is now the durable copy of record.
        was_writable = [(v, v.read_only) for _, v in vols]
        for v, _ in was_writable:
            v.read_only = True
        total = sum(os.path.getsize(v._base + ".dat") for _, v in vols)
        stages: dict = {}
        shared = {"state": "running", "kind": "fleet_convert",
                  "bytes_done": 0, "total": total, "cancel": False,
                  "error": None, "started": time.time(),
                  "volumes": [vid for vid, _ in vols], "stages": stages}
        for vid, _ in vols:
            self._ec_jobs[vid] = shared

        def run():
            for _, v in vols:
                v.flush()  # buffered .dat AND .idx — the mmap'd snapshot
                #            must hold every committed needle
            rep = _fleet.convert_volumes(
                [v._base for _, v in vols], codec=codec,
                large_block=large, small_block=small,
                progress=lambda n: shared.__setitem__("bytes_done", n),
                cancel=lambda: shared["cancel"],
                stats=stages)
            for _, v in vols:
                ec_files.write_sorted_ecx(v._base + ".idx")
            metrics.EC_ENCODE_BYTES.labels(
                stages.get("backend", "unknown")).inc(total)
            return rep

        def settle_failed():
            """Volumes whose shard set committed before the run died stay
            frozen (the EC set is their copy of record) and get the .ecx
            the success path would have written; only uncommitted ones —
            whose .tmp shards were rolled back — thaw."""
            committed = set(stages.get("committed_bases") or [])
            for v, ro in was_writable:
                if v._base in committed:
                    try:
                        ec_files.write_sorted_ecx(v._base + ".idx")
                    except OSError:
                        log.warning("post-abort .ecx write failed for %s",
                                    v._base, exc_info=True)
                else:
                    v.read_only = ro

        try:
            report = await asyncio.to_thread(run)
        except ec_files.EncodeCancelled:
            shared["state"] = "cancelled"
            settle_failed()
            return web.json_response({"error": "cancelled"}, status=409)
        except Exception as e:
            shared["state"] = "failed"
            shared["error"] = str(e)
            settle_failed()
            raise
        shared["state"] = "done"
        shared["bytes_done"] = total
        await self._heartbeat_once()  # the new shard sets reach the topo
        return web.json_response(
            {"converted": [vid for vid, _ in vols], "skipped": skipped,
             "bytes": report["bytes"], "units": report["units"],
             "devices": report["devices"], "wall_s": report["wall_s"],
             "large_block_bytes": large, "small_block_bytes": small})

    async def handle_ec_progress(self, req: web.Request) -> web.Response:
        """Observability for a long-running encode (weak spot the reference
        covers with streamed gRPC progress)."""
        vid = int(req.query.get("volumeId", "0"))
        job = self._ec_jobs.get(vid)
        if job is None:
            return web.json_response({"error": "no encode job"}, status=404)
        # dict() is a single C-level copy (atomic under the GIL); the
        # worker thread inserts keys into job AND its nested stages dict
        # while we serialize, and json.dumps iterating the live dict
        # would raise "dictionary changed size during iteration"
        snap = {k: dict(v) if isinstance(v, dict) else v
                for k, v in dict(job).items()}
        return web.json_response(snap)

    async def handle_ec_cancel(self, req: web.Request) -> web.Response:
        body = await req.json()
        job = self._ec_jobs.get(body["volume"])
        if job is None or job["state"] != "running":
            return web.json_response({"error": "no running encode"},
                                     status=404)
        job["cancel"] = True
        return web.json_response({"ok": True})

    async def _run_ec_job(self, vids: list[int], job: dict, work,
                          answer) -> web.Response:
        """Run `work(progress, cancel)` on a thread as the EC job `job`,
        registered under every vid of `vids`, so that /admin/ec/progress on
        any of them observes it and /admin/ec/cancel on any of them stops
        it.  The answer is `answer(result, None)`, or, where the work
        raised, the job settled cancelled or failed and `answer(None, e)`
        with the `error`: 409 for a cancel, 400 for a code the backend does
        not carry (refused before any tmp file), 500 for anything else.
        The job keeps the answer (`answer` on /admin/ec/progress), for a
        client whose call timed out while the job went on."""
        for vid in vids:
            self._ec_jobs[vid] = job
        from seaweedfs_tpu.ops import codecs as _codecs
        try:
            result = await asyncio.to_thread(
                work, lambda n: job.__setitem__("bytes_done", n),
                lambda: job["cancel"])
        except Exception as e:
            if isinstance(e, ec_files.EncodeCancelled):
                status, error = 409, "cancelled"
                job["state"] = "cancelled"
            else:
                status = 400 if isinstance(e, _codecs.CodecUnsupported) \
                    else 500
                error = job["error"] = str(e)
                job["state"] = "failed"
                if status == 500:
                    log.warning("ec %s of volumes %s failed", job["kind"],
                                vids, exc_info=True)
            job["answer"] = dict(answer(None, e), error=error)
            return web.json_response(job["answer"], status=status)
        job["answer"] = answer(result, None)
        job["state"] = "done"
        job["bytes_done"] = job["total"]
        return web.json_response(job["answer"])

    @staticmethod
    def _rebuild_job(kind: str, sets: list[tuple], stages: dict,
                     **extra) -> dict:
        """A rebuild's job record; `total` is the survivor bytes a decode
        of each (base, code spec) in `sets` reads."""
        total = 0
        for base, spec in sets:
            present = [i for i in range(spec.n)
                       if os.path.exists(base + layout.to_ext(i))]
            if present:
                total += os.path.getsize(
                    base + layout.to_ext(present[0])) * spec.k
        return {"state": "running", "kind": kind, **extra, "bytes_done": 0,
                "total": total, "cancel": False, "error": None,
                "started": time.time(), "stages": stages}

    async def handle_ec_rebuild(self, req: web.Request) -> web.Response:
        """VolumeEcShardsRebuild (volume_grpc_erasure_coding.go:84).

        Registers under the same per-vid job state as encode, so
        /admin/ec/progress and /admin/ec/cancel observe and abort a
        long-running rebuild identically.  `{"volumes": [...]}` is a
        rebuilder's backlog in one call (`_handle_ec_rebuild_volumes`);
        `{"volume": v}` is its case of one (`rebuild_ec_files`), answered
        `{"rebuilt": [shard ids]}`.  The commit is by rename either way: a
        failed or cancelled rebuild of one volume leaves its previous set
        untouched."""
        body = await req.json()
        if "volumes" in body:
            return await self._handle_ec_rebuild_volumes(body)
        vid = body["volume"]
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({"error": "no shards here"}, status=404)
        if self._ec_jobs.get(vid, {}).get("state") == "running":
            return web.json_response({"error": "ec job already running"},
                                     status=409)
        reduced = body.get("reduced")
        # codec identity: the caller's tag (master plans carry it) wins,
        # else the local .vif — a rebuilder holding copied shards but no
        # sidecar must still decode with the right matrix
        from seaweedfs_tpu.ops import codecs as _codecs
        spec = _codecs.parse_tag(body.get("codec") or
                                 (ec_files.read_vif(base) or {}).get("codec"))
        stages: dict = {}
        job = self._rebuild_job(
            "rebuild_reduced" if reduced else "rebuild", [(base, spec)],
            stages, codec=spec.tag)
        if not reduced:
            return await self._run_ec_job(
                [vid], job,
                lambda progress, cancel: ec_files.rebuild_ec_files(
                    base, progress=progress, cancel=cancel, stats=stages,
                    codec_tag=spec.tag),
                lambda rebuilt, e: {} if e else {"rebuilt": rebuilt})
        # reduced-read path: no survivor copies land here — each helper
        # node ships XOR-combinable partials instead
        # (storage/ec/ec_files.rebuild_ec_reduced)
        from seaweedfs_tpu.ops import regen as _regen
        lost = sorted(int(s) for s in reduced.get("lost", []))
        groups = [g for g in (reduced.get("groups") or [])
                  if g.get("node") and g["node"] != self.url]
        if reduced.get("shard_size"):
            for g in groups:
                g.setdefault("shard_size", reduced["shard_size"])

        def answer(result, e) -> dict:
            if isinstance(e, _regen.HelperDied):
                # re-planning exhausted its substitutes: the master retries
                # / falls back to naive copies, and needs to know how hard
                # we tried and who killed us — a bare 500 hides the replan
                # story
                return {"helper": e.node or "<local>",
                        "helper_shards": list(e.shards),
                        "replans": stages.get("replans", 0),
                        "dead_helpers": stages.get("dead_helpers", [])}
            return {} if e else result

        resp = await self._run_ec_job(
            [vid], job,
            lambda progress, cancel: ec_files.rebuild_ec_reduced(
                base, lost, groups,
                self._partial_fetcher(vid, alpha=spec.alpha),
                d=reduced.get("d"), progress=progress, cancel=cancel,
                stats=stages, codec_tag=spec.tag),
            answer)
        if resp.status == 200:
            await self._heartbeat_once()
        return resp

    async def _handle_ec_rebuild_volumes(self, body: dict) -> web.Response:
        """`ec.rebuild`'s loop on the rebuilder, in one call: every listed
        volume's missing shards rebuilt in one pipeline
        (ec_files.rebuild_ec_volumes), each under the code its `.vif`
        names, each committed by rename as soon as its rows are written.
        One job is registered under every vid it works on, as a fleet
        conversion's is.  A volume with no shard files here, an EC job of
        its own running, nothing missing or fewer survivors than its code's
        k is answered under `skipped` with its files untouched, and the
        others go on.  The answer: `volumes` (the list asked for),
        `rebuilt` {vid: shard ids} of the volumes committed, `shard_files`
        (files written), `skipped` {vid: why}.  A cancel (409) or a failure
        (500) answers the same keys with `error` and `untouched`: the
        volumes committed before it stay committed, the one in flight and
        every one after it are as they were before the call."""
        vids: list[int] = []
        for v_ in body.get("volumes") or []:
            try:
                vid = int(v_)
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": f"not a volume id: {v_!r}"}, status=400)
            if vid not in vids:
                vids.append(vid)
        if not vids:
            return web.json_response({"error": "no volumes listed"},
                                     status=400)
        skipped: dict[str, str] = {}
        bases: dict[str, int] = {}
        for vid in vids:
            base = self._ec_base(vid)
            if base is None:
                skipped[str(vid)] = "no shards here"
            elif self._ec_jobs.get(vid, {}).get("state") == "running":
                skipped[str(vid)] = "ec job already running"
            else:
                bases[base] = vid
        from seaweedfs_tpu.ops import codecs as _codecs
        stages: dict = {}
        job = self._rebuild_job(
            "rebuild",
            [(base, _codecs.parse_tag(
                (ec_files.read_vif(base) or {}).get("codec")))
             for base in bases],
            stages, volumes=list(bases.values()))

        def answer(report, e) -> dict:
            report = getattr(e, "report", {}) if e else report
            rebuilt = {str(bases[b]): ids
                       for b, ids in report.get("rebuilt", {}).items()}
            skipped.update((str(bases[b]), why)
                           for b, why in report.get("skipped", {}).items())
            out = {"volumes": vids, "rebuilt": rebuilt,
                   "shard_files": sum(map(len, rebuilt.values())),
                   "skipped": skipped}
            if e:
                out["untouched"] = [v for v in bases.values()
                                    if str(v) not in rebuilt and
                                    str(v) not in skipped]
            return out

        return await self._run_ec_job(
            list(bases.values()), job,
            lambda progress, cancel: ec_files.rebuild_ec_volumes(
                list(bases), progress=progress, cancel=cancel, stats=stages),
            answer)

    async def handle_ec_mount(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = body["volume"]
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({"error": "no shard files"}, status=404)
        loc = next(l for l in self.store.locations
                   if base.startswith(l.directory))
        old = loc.ec_volumes.pop(vid, None)
        if old is not None:
            old.close()
        loc.ec_volumes[vid] = ecv.EcVolume(base)
        await self._heartbeat_once()
        return web.json_response({"shards": loc.ec_volumes[vid].shard_ids()})

    async def handle_ec_unmount(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = body["volume"]
        for loc in self.store.locations:
            ev = loc.ec_volumes.pop(vid, None)
            if ev is not None:
                ev.close()
        await self._heartbeat_once()
        return web.json_response({})

    async def handle_ec_delete_shards(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid, shards = body["volume"], body.get("shards", [])
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({})
        mounted = self.store.get_ec_volume(vid)
        for sid in shards:
            p = base + layout.to_ext(sid)
            if os.path.exists(p):
                os.remove(p)
            if mounted is not None:
                f = mounted.shards.pop(sid, None)
                if f is not None:
                    f.close()
                # a purged shard's scrub verdicts die with its file — a
                # rebuilt replacement must not inherit the quarantine
                mounted.clear_quarantine(sid)
        # if no shards remain anywhere, drop index files too
        if not any(os.path.exists(base + layout.to_ext(i))
                   for i in range(layout.MAX_TOTAL_SHARDS)):
            for ext in (".ecx", ".ecj"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
        await self._heartbeat_once()
        return web.json_response({})

    async def handle_ec_copy(self, req: web.Request) -> web.Response:
        """VolumeEcShardsCopy (volume_grpc_erasure_coding.go:126): PULL shard
        files from a peer (the reference's CopyFile stream, as HTTP)."""
        body = await req.json()
        vid, source = body["volume"], body["source"]
        shards = body.get("shards", [])
        collection = body.get("collection", "")
        exts = [layout.to_ext(s) for s in shards]
        if body.get("copy_ecx", True):
            exts += [".ecx", ".vif"]
        if body.get("copy_ecj", False):
            exts.append(".ecj")
        loc = min(self.store.locations, key=lambda l: len(l.volumes))
        base = loc.base_path(vid, collection)
        for ext in exts:
            name = os.path.basename(base + ext)
            try:
                async with self._session.get(
                        f"{_tls_scheme()}://{source}/admin/file",
                        params={"name": name}) as r:
                    if r.status != 200:
                        if ext in (".ecj", ".vif"):
                            continue  # optional files
                        return web.json_response(
                            {"error": f"pull {name} from {source}: {r.status}"},
                            status=500)
                    with open(base + ext, "wb") as f:
                        async for chunk in r.content.iter_chunked(1 << 20):
                            # streamed reads bypass the aiohttp trace
                            # hooks: book the shard bytes explicitly
                            netflow.account("recv",
                                            netflow.current_class(),
                                            "volume", len(chunk))
                            f.write(chunk)
            except aiohttp.ClientError as e:
                return web.json_response({"error": str(e)}, status=500)
        loc.collections.setdefault(vid, collection)
        return web.json_response({})

    async def handle_volume_copy(self, req: web.Request) -> web.Response:
        """VolumeCopy (reference: volume_grpc_copy.go:199-223 doCopyFile):
        pull a whole volume's .dat/.idx from a peer and mount it here.
        Used by volume.balance / volume.fix.replication."""
        body = await req.json()
        vid, source = body["volume"], body["source"]
        collection = body.get("collection", "")
        # staging=True keeps the copy OUT of the write path for the whole
        # move: hidden from heartbeats (no master lookup / replicate
        # fan-out can reach it) and read-only, with an on-disk .staging
        # marker so a crash mid-move never boots it as live data.
        # finalize=True flips it live after the frozen-source catch-up —
        # the reference gets the same safety by mounting only at the end
        # (command_volume_move.go LiveMoveVolume).
        staging = bool(body.get("staging"))
        finalize = bool(body.get("finalize"))
        existing = self.store.get_volume(vid)
        if existing is not None:
            # incremental catch-up (reference:
            # volume_grpc_copy_incremental.go): .dat is append-only, so
            # pull only the tail past our size, then refresh the .idx
            resp = await self._volume_copy_incremental(
                existing, vid, source, collection)
            if finalize and resp.status == 200 and \
                    getattr(existing, "staging", False):
                # only a staged copy flips live here — a pre-existing
                # replica that is read-only for structural reasons
                # (remote tier, sorted-file map) must stay read-only
                try:
                    os.remove(existing._base + ".staging")
                except OSError:
                    pass
                existing.staging = False
                existing.read_only = False
                await self._heartbeat_once()
            return resp
        loc = min(self.store.locations, key=lambda l: len(l.volumes))
        base = loc.base_path(vid, collection)
        # pull into .cpd/.cpx temp names, rename only when both succeed, so
        # a failed copy can't leave a partial .dat that load_existing would
        # mount as a live volume (reference: volume_vacuum.go temp names)
        tmp_ext = {".dat": ".cpd", ".idx": ".cpx"}
        # CRC32 of each pulled file computed WHILE streaming: the move
        # orchestrator compares it against the source's own digest, so a
        # torn transfer (or bit flips in transit) can never commit
        import zlib as _zlib
        crcs: dict[str, int] = {}
        try:
            for ext in (".dat", ".idx"):
                name = os.path.basename(base + ext)
                crc = 0
                async with self._session.get(
                        f"{_tls_scheme()}://{source}/admin/file",
                        params={"name": name}) as r:
                    if r.status != 200:
                        raise OSError(
                            f"pull {name} from {source}: HTTP {r.status}")
                    with open(base + tmp_ext[ext], "wb") as f:
                        async for chunk in r.content.iter_chunked(1 << 20):
                            # streamed reads bypass the aiohttp trace
                            # hooks (chunk events fire for buffered
                            # read()s only): book the bytes explicitly
                            netflow.account("recv",
                                            netflow.current_class(),
                                            "volume", len(chunk))
                            crc = _zlib.crc32(chunk, crc)
                            f.write(chunk)
                crcs[ext.lstrip(".")] = crc
            if staging:
                # marker lands BEFORE the .dat appears: a crash between the
                # renames can only leave a marked (= never-booted) copy
                with open(base + ".staging", "w"):
                    pass
            for ext in (".dat", ".idx"):
                os.replace(base + tmp_ext[ext], base + ext)
        except (aiohttp.ClientError, OSError) as e:
            for ext in (".cpd", ".cpx", ".staging"):
                try:
                    os.remove(base + ext)
                except OSError:
                    pass
            return web.json_response({"error": str(e)}, status=500)
        from seaweedfs_tpu.storage.volume import Volume
        try:
            vol = await asyncio.to_thread(Volume, loc.directory, collection,
                                          vid)
        except Exception as e:
            return web.json_response({"error": f"load: {e}"}, status=500)
        if staging:
            vol.staging = True
            vol.read_only = True
        loc.volumes[vid] = vol
        loc.collections[vid] = collection
        if not staging:  # staged copies stay invisible until finalize
            await self._heartbeat_once()
        return web.json_response({"file_count": vol.info().file_count,
                                  "crc": crcs})

    async def handle_volume_move(self, req: web.Request) -> web.Response:
        """POST /admin/volume/move {"volume", "target"}: rebalance one
        volume off this server — the autopilot balancing actuator.
        Protocol: freeze writes → staged copy to the target → verify the
        target's streamed CRC against the source .dat → commit (the
        finalizing catch-up flips the staged copy live) → retire the
        source copy.  Every byte books as netflow class=rebalance.

        Abortable mid-failure with NO partial state: until the finalize
        succeeds the target copy is staged (read-only, heartbeat-
        invisible, .staging-marked on disk) and the source keeps serving
        reads; any failure deletes the staged copy (best-effort — a
        KILLED target deletes its own .staging leftovers at boot) and
        re-thaws the source to its prior writability.  After the
        finalize the target IS the volume, so the source retires
        unconditionally — two live copies of a single-replica volume
        would silently diverge."""
        body = await req.json()
        try:
            vid = int(body["volume"])
            target = str(body["target"])
        except (KeyError, TypeError, ValueError):
            return web.json_response(
                {"error": "volume and target required"}, status=400)
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        if target == self.url or not target:
            return web.json_response({"error": "bad target"}, status=400)
        # single-flight per vid (handlers run on one event loop, so the
        # check-and-add is atomic): a concurrent second move would stage
        # AND commit a second live copy
        if vid in self._moves_active or getattr(v, "staging", False):
            return web.json_response({"error": "volume is mid-move"},
                                     status=409)
        self._moves_active.add(vid)
        try:
            return await self._volume_move(vid, v,
                                           str(body.get("collection")
                                               or ""), target)
        finally:
            self._moves_active.discard(vid)

    async def _volume_move(self, vid: int, v, collection: str,
                           target: str) -> web.Response:
        from seaweedfs_tpu.utils.http import post_json
        import zlib as _zlib
        if not collection:
            for loc in self.store.locations:
                if vid in loc.volumes:
                    collection = loc.collections.get(vid, "")
                    break

        async def post(path: str, pbody: dict,
                       timeout: float = 600.0) -> dict:
            return await post_json(self._session, target, path, pbody,
                                   timeout)

        def dat_crc() -> int:
            crc = 0
            with open(v.dat_path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    crc = _zlib.crc32(chunk, crc)
            return crc

        was_ro = v.read_only
        copy_body = {"volume": vid, "source": self.url,
                     "collection": collection, "staging": True}
        try:
            with netflow.flow("rebalance"), \
                    trace.span("volume.move", vid=vid, target=target,
                               bytes=v.data_size()):
                # freeze FIRST: against a frozen source the staged copy
                # is complete the moment its CRC matches — no append
                # tail to chase, the finalizing catch-up moves 0 bytes
                v.read_only = True
                await asyncio.to_thread(v.flush)
                data = await post("/admin/volume/copy", copy_body)
                if data.get("incremental"):
                    # the target already held a live replica: refuse
                    # WITHOUT the generic abort below — its cleanup
                    # deletes the target copy, which here would destroy
                    # a real replica, not our staging leftovers
                    v.read_only = was_ro
                    metrics.VOLUME_MOVES.labels("aborted").inc()
                    return web.json_response(
                        {"error": f"{target} already holds volume "
                                  f"{vid}; move refused (that is "
                                  "volume.fix.replication's job)"},
                        status=409)
                remote_crc = (data.get("crc") or {}).get("dat")
                local_crc = await asyncio.to_thread(dat_crc)
                if remote_crc != local_crc:
                    raise RuntimeError(
                        f"CRC mismatch after copy: source {local_crc} "
                        f"vs target {remote_crc}")
                await post("/admin/volume/copy",
                           dict(copy_body, finalize=True))
        except Exception as e:
            try:
                await post("/admin/volume/delete", {"volume": vid},
                           timeout=10.0)
            except Exception:
                pass  # dead target: its boot cleanup removes the stage
            v.read_only = was_ro
            metrics.VOLUME_MOVES.labels("aborted").inc()
            return web.json_response({"error": str(e)}, status=500)
        await asyncio.to_thread(self.store.delete_volume, vid)
        await self._heartbeat_once()
        metrics.VOLUME_MOVES.labels("ok").inc()
        return web.json_response({"moved": vid, "target": target,
                                  "crc": local_crc})

    async def handle_volume_unconvert(self, req: web.Request
                                      ) -> web.Response:
        """POST /admin/volume/unconvert {"volume"}: promote an EC volume
        back to the replicated/mmap fast path — the autopilot tiering
        promote actuator, reversing the fleet-convert demote.  Decodes
        the local data shards back into a .dat under a temp name
        (tmp+rename, the fleet_convert commit contract: a crash
        mid-decode never leaves a half-written .dat a restart would
        mount as live data), rebuilds the .idx from the .ecx (replaying
        .ecj tombstones), mounts, THAWS (the write-freeze the conversion
        imposed ends here), and retires the local shard set.  When the
        conversion's frozen .dat is still on disk (the fleet-convert
        contract keeps the source volume mounted read-only) the decode
        is skipped outright — the thaw alone promotes.  Registers under
        the shared per-vid job table so /admin/ec/progress observes a
        long decode."""
        body = await req.json()
        try:
            vid = int(body["volume"])
        except (KeyError, TypeError, ValueError):
            return web.json_response({"error": "volume required"},
                                     status=400)
        base = self._ec_base(vid)
        if base is None or not os.path.exists(base + ".ecx"):
            return web.json_response({"error": "no ec volume here"},
                                     status=404)
        if self._ec_jobs.get(vid, {}).get("state") == "running":
            return web.json_response({"error": "ec job already running"},
                                     status=409)
        existing = self.store.get_volume(vid)
        job = {"state": "running", "kind": "unconvert", "bytes_done": 0,
               "total": 0, "cancel": False, "error": None,
               "started": time.time(), "stages": {}}
        self._ec_jobs[vid] = job

        def decode() -> bool:
            if existing is not None and \
                    os.path.exists(existing._base + ".dat"):
                return False  # frozen .dat survives: thaw-only promote
            from seaweedfs_tpu.ops import codecs as _codecs
            spec = _codecs.parse_tag(
                (ec_files.read_vif(base) or {}).get("codec"))
            missing = [i for i in range(spec.k)
                       if not os.path.exists(base + layout.to_ext(i))]
            if missing:
                ec_files.rebuild_ec_files(base, codec_tag=spec.tag)
            dat_size = ec_files.find_dat_file_size(base)
            job["total"] = dat_size
            dat_tmp, idx_tmp = base + ".dat.unc", base + ".idx.unc"
            try:
                ec_files.write_dat_file(base, dat_size, out_path=dat_tmp)
                ec_files.write_idx_from_ecx(base + ".ecx", idx_tmp)
            except BaseException:
                for p in (dat_tmp, idx_tmp):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                raise
            # .idx lands first: a .dat whose .idx is missing rebuilds
            # its map at mount, but an orphan .idx mounts nothing
            os.replace(idx_tmp, base + ".idx")
            os.replace(dat_tmp, base + ".dat")
            job["bytes_done"] = dat_size
            return True

        try:
            with trace.span("volume.unconvert", vid=vid):
                decoded = await asyncio.to_thread(decode)
        except Exception as e:
            job["state"] = "failed"
            job["error"] = str(e)
            return web.json_response({"error": str(e)}, status=500)
        loc = next(l for l in self.store.locations
                   if base.startswith(l.directory))
        v = existing
        if v is None:
            stem = os.path.basename(base)
            collection = body.get("collection") or \
                loc.collections.get(vid) or \
                (stem[: -(len(str(vid)) + 1)]
                 if stem.endswith(f"_{vid}") else "")
            from seaweedfs_tpu.storage.volume import Volume
            try:
                v = await asyncio.to_thread(Volume, loc.directory,
                                            collection, vid)
            except Exception as e:
                job["state"] = "failed"
                job["error"] = str(e)
                return web.json_response({"error": f"load: {e}"},
                                         status=500)
            loc.volumes[vid] = v
            loc.collections[vid] = collection
        # retire the EC set BEFORE the thaw, .ecx first: load_existing
        # keys EC mounts on the .ecx, so once it is gone a crash at any
        # later point boots the plain volume alone — never a writable
        # .dat NEXT TO a mountable stale shard set the repair planner
        # would treat as authoritative (ledger rule: shard entry wins)
        for l in self.store.locations:
            ev = l.ec_volumes.pop(vid, None)
            if ev is not None:
                ev.close()
        for ext in (".ecx", ".ecj", ".vif"):
            if os.path.exists(base + ext):
                os.remove(base + ext)
        removed = []
        for i in range(layout.MAX_TOTAL_SHARDS):
            p = base + layout.to_ext(i)
            if os.path.exists(p):
                os.remove(p)
                removed.append(i)
        v.read_only = False  # the thaw: the mmap fast path serves again
        job["state"] = "done"
        await self._heartbeat_once()
        return web.json_response({"volume": vid, "decoded": decoded,
                                  "thawed": True,
                                  "shards_retired": removed})

    async def handle_tier_move(self, req: web.Request) -> web.Response:
        """Move a sealed volume's .dat to a remote tier (reference:
        volume_grpc_tier.go VolumeTierMoveDatToRemote)."""
        body = await req.json()
        vid = body["volume"]
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        kind = body.get("kind", "local")
        options = body.get("options", {})
        try:
            await asyncio.to_thread(v.tier_move, kind, options,
                                    body.get("key"))
        except (ValueError, TypeError, OSError, PermissionError) as e:
            return web.json_response({"error": str(e)}, status=500)
        await self._heartbeat_once()
        return web.json_response({"backend": v.backend_kind})

    async def handle_tier_download(self, req: web.Request) -> web.Response:
        """Pull a tiered volume's .dat back from the remote (reference:
        volume_grpc_tier.go VolumeTierMoveDatFromRemote)."""
        body = await req.json()
        vid = body["volume"]
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        try:
            await asyncio.to_thread(
                v.tier_download, bool(body.get("delete_remote")))
        except (ValueError, TypeError, OSError, PermissionError) as e:
            return web.json_response({"error": str(e)}, status=500)
        await self._heartbeat_once()
        return web.json_response({"backend": v.backend_kind})

    async def _volume_copy_incremental(self, v, vid: int, source: str,
                                       collection: str) -> web.Response:
        """Stage the source's .dat tail and .idx WITHOUT touching the live
        volume, then apply both atomically under the volume lock
        (Volume.apply_catch_up) — concurrent writers either land before
        the size snapshot (copied) or make the apply fail cleanly."""
        name = os.path.basename(v.dat_path)
        # divergence guard: a vacuumed source has a different compaction
        # revision; appending its tail to our pre-vacuum bytes would
        # corrupt the replica even when its file is larger
        try:
            async with self._session.get(
                    f"{_tls_scheme()}://{source}/admin/file",
                    params={"name": name},
                    headers={"Range": "bytes=0-7"}) as r:
                if r.status not in (200, 206):
                    return web.json_response(
                        {"error": f"probe super block: HTTP {r.status}"},
                        status=500)
                remote_sb = await r.read()
        except aiohttp.ClientError as e:
            return web.json_response({"error": str(e)}, status=500)
        from seaweedfs_tpu.storage.super_block import SuperBlock
        try:
            remote_rev = SuperBlock.from_bytes(
                remote_sb.ljust(64, b"\0")).compaction_revision
        except Exception:
            return web.json_response({"error": "bad source super block"},
                                     status=500)
        if remote_rev != v.super_block.compaction_revision:
            return web.json_response(
                {"error": "source compaction revision differs; full "
                          "re-copy required (delete the local copy)"},
                status=409)

        local_size = v.data_size()
        tail_path = v.dat_path + ".cptail"
        appended_hint = 0
        try:
            async with self._session.get(
                    f"{_tls_scheme()}://{source}/admin/file",
                    params={"name": name},
                    headers={"Range": f"bytes={local_size}-"}) as r:
                if r.status == 416:
                    cr = r.headers.get("Content-Range", "")  # "bytes */N"
                    try:
                        src_size = int(cr.rpartition("/")[2])
                    except ValueError:
                        src_size = local_size
                    if src_size < local_size:
                        return web.json_response(
                            {"error": "local replica is ahead of the "
                                      "source; refusing incremental copy"},
                            status=409)
                    with open(tail_path, "wb"):
                        pass
                elif r.status == 206:
                    with open(tail_path, "wb") as f:
                        async for chunk in r.content.iter_chunked(1 << 20):
                            netflow.account("recv",
                                            netflow.current_class(),
                                            "volume", len(chunk))
                            f.write(chunk)
                            appended_hint += len(chunk)
                elif r.status == 200:
                    return web.json_response(
                        {"error": "source ignored the Range; refusing "
                                  "incremental copy"}, status=409)
                else:
                    return web.json_response(
                        {"error": f"pull tail: HTTP {r.status}"}, status=500)
            idx_name = os.path.basename(v.idx_path)
            async with self._session.get(
                    f"{_tls_scheme()}://{source}/admin/file",
                    params={"name": idx_name}) as r:
                if r.status != 200:
                    return web.json_response(
                        {"error": f"pull idx: HTTP {r.status}"}, status=500)
                idx_raw = await r.read()
            try:
                appended = await asyncio.to_thread(
                    v.apply_catch_up, local_size, tail_path, idx_raw)
            except (RuntimeError, PermissionError) as e:
                return web.json_response({"error": str(e)}, status=409)
        except aiohttp.ClientError as e:
            return web.json_response({"error": str(e)}, status=500)
        finally:
            try:
                os.remove(tail_path)
            except OSError:
                pass
        await self._heartbeat_once()
        return web.json_response({"incremental": True,
                                  "appended_bytes": appended})

    async def handle_volume_needles(self, req: web.Request) -> web.Response:
        """List needle ids + sizes of a volume (fsck / check.disk support;
        the reference streams .idx via VolumeCopy's CopyFile or
        VolumeNeedleStatus)."""
        vid = int(req.query["volume"])
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        limit = int(req.query.get("limit", "1000000"))
        needles = []
        for nid, (_off, size) in v.nm.items():
            if size >= 0:
                needles.append(nid)
                if len(needles) >= limit:
                    break
        return web.json_response({"volume": vid, "count": len(needles),
                                  "needles": needles})

    async def handle_query(self, req: web.Request) -> web.Response:
        """S3-Select-style JSON query pushdown over a volume's needles
        (reference: volume_server.proto:107 Query rpc +
        weed/server/volume_grpc_query.go, weed/query/json).  Body:
        {volume, filter: {field, op, value}?, projections: [fields]?,
        limit?} -> NDJSON of matching (projected) documents."""
        # same read-auth bar as GET /{fid}: a configured read key gates
        # bulk content export too
        if self.security is not None and self.security.volume_read:
            token = sjwt.token_from_request(req.headers, req.query)
            try:
                sjwt.decode_jwt(self.security.volume_read, token)
            except sjwt.JwtError as e:
                return web.json_response({"error": str(e)}, status=401)
        import json as _json
        body = await req.json()
        vid = body["volume"]
        v = self.store.get_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        flt = body.get("filter")
        projections = body.get("projections")
        limit = int(body.get("limit", 10000))

        def match(doc: dict) -> bool:
            if not flt:
                return True
            val = doc.get(flt["field"])
            want = flt.get("value")
            op = flt.get("op", "=")
            try:
                if op in ("=", "=="):
                    return val == want
                if op == "!=":
                    return val != want
                if op == ">":
                    return val is not None and val > want
                if op == ">=":
                    return val is not None and val >= want
                if op == "<":
                    return val is not None and val < want
                if op == "<=":
                    return val is not None and val <= want
                if op == "like":
                    return isinstance(val, str) and str(want) in val
            except TypeError:
                return False
            return False

        def run_query() -> list[bytes]:
            rows = []
            for offset, n in v.scan():
                if not n.data or not v.has_needle(n.id):
                    continue
                live = v.nm.get(n.id)
                if live is None or live[0] != offset // t.NEEDLE_PADDING_SIZE:
                    continue
                try:
                    doc = _json.loads(n.data)
                except (ValueError, UnicodeDecodeError):
                    continue
                if not isinstance(doc, dict) or not match(doc):
                    continue
                if projections:
                    doc = {k: doc.get(k) for k in projections}
                rows.append(_json.dumps(doc, separators=(",", ":")).encode())
                if len(rows) >= limit:
                    break
            return rows

        rows = await asyncio.to_thread(run_query)
        return web.Response(body=b"\n".join(rows) + (b"\n" if rows else b""),
                            content_type="application/x-ndjson")

    async def handle_file_pull(self, req: web.Request) -> web.StreamResponse:
        """Serve a volume/ec file by basename for peer pulls (source side of
        VolumeEcShardsCopy / VolumeCopy)."""
        if self._fault_delay_file_pull > 0:
            await asyncio.sleep(self._fault_delay_file_pull)
        name = req.query.get("name", "")
        if "/" in name or ".." in name:
            return web.json_response({"error": "bad name"}, status=400)
        ok_ext = name.endswith((".dat", ".idx")) or \
            any(name.endswith(e) for e in EC_FILE_EXTS)
        if not ok_ext:
            return web.json_response({"error": "bad extension"}, status=400)
        if name.endswith((".dat", ".idx")):
            # flush buffered index/data writes so peers pull a current copy
            stem = name.rsplit(".", 1)[0]
            try:
                vid = int(stem.rsplit("_", 1)[-1] if "_" in stem else stem)
            except ValueError:
                vid = -1
            v = self.store.get_volume(vid)
            if v is not None:
                await asyncio.to_thread(v.flush)
        for loc in self.store.locations:
            p = os.path.join(loc.directory, name)
            if os.path.exists(p):
                return web.FileResponse(p)
        return web.json_response({"error": "file not found"}, status=404)

    # -- maintenance: scrub + fault injection ----------------------------

    def _report_scrub(self, summary: dict) -> None:
        """Push a scrub pass's verdicts to the master's repair planner.
        Runs on the scrub thread -> blocking client."""
        import json as _json
        import urllib.request
        body = _json.dumps({"node": self.url, "ts": summary.get("ts"),
                            "volumes": summary.get("volumes", {})}).encode()
        try:
            r = urllib.request.Request(
                f"{_tls_scheme()}://{self.master_url}"
                "/maintenance/scrub_report", data=body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(r, timeout=10).close()
        except OSError as e:
            log.warning("scrub report to %s failed: %s", self.master_url, e)

    def _loopback_only(self, req: web.Request) -> web.Response | None:
        # same gate as the /debug/* surface: one copy (stats/trace.py)
        return trace.loopback_error(req)

    async def handle_scrub(self, req: web.Request) -> web.Response:
        """Run one scrub pass NOW and return its summary (also reported
        to the master).  Operator/test hook; the background loop covers
        steady state."""
        err = self._loopback_only(req)
        if err is not None:
            return err
        s = self.scrubber
        if s is None:
            from seaweedfs_tpu.maintenance.scrub import Scrubber
            s = Scrubber(self.store, report=None,
                         shard_reader_factory=self._shard_reader)
        summary = await asyncio.to_thread(s.scrub_once)
        await asyncio.to_thread(self._report_scrub, summary)
        return web.json_response(summary)

    async def handle_scrub_rate(self, req: web.Request) -> web.Response:
        """Retune the background scrubber's sustained rate live —
        the master's interference governor pushes here each retune
        (stats/interference.py), marking itself with ``governed: true``
        so an operator's explicit {"mbps": 0} pause is never silently
        un-paused by the governor's periodic re-pushes.  Not
        loopback-gated: like the other /admin control surfaces this is
        cluster plumbing the master drives remotely.  Applies mid-pass;
        a node with scrubbing disabled (WEEDTPU_SCRUB_MBPS=0) reports
        mbps null."""
        try:
            body = await req.json()
            scale = body.get("scale")
            mbps = float(scale) if scale is not None \
                else float(body.get("mbps"))
        except (ValueError, TypeError, AttributeError):
            # AttributeError: a valid-JSON non-object body ('[2.5]')
            # has no .get — still the caller's 400, not our 500
            return web.json_response({"error": "mbps or scale required"},
                                     status=400)
        if self.scrubber is None:
            return web.json_response({"mbps": None})
        if scale is not None:
            # the governor's form: a fraction of THIS node's configured
            # rate, so heterogeneous per-node WEEDTPU_SCRUB_MBPS values
            # are scaled, never raised to the master's ceiling
            out = self.scrubber.apply_governed_scale(mbps)
        else:
            out = self.scrubber.set_mbps(
                mbps, governed=bool(body.get("governed")))
        return web.json_response(
            {"mbps": out,
             "operator_paused": self.scrubber.operator_paused})

    async def handle_faults(self, req: web.Request) -> web.Response:
        """Test-only fault injection (maintenance/faults.py): flip bits,
        delete shards, delay peer shard reads.  Loopback only."""
        err = self._loopback_only(req)
        if err is not None:
            return err
        from seaweedfs_tpu.maintenance import faults as _faults
        body = await req.json()
        applied = []
        for f in body.get("faults", []):
            if f.get("action") == "delay_shard_read":
                self._fault_delay_shard_read = float(f.get("ms", 0)) / 1000.0
                applied.append(dict(f, ok=True))
                continue
            if f.get("action") == "delay_file_pull":
                # stall peer file pulls (/admin/file) — holds a volume
                # copy/move open long enough for chaos cells to kill a
                # node mid-transfer deterministically
                self._fault_delay_file_pull = float(f.get("ms", 0)) / 1000.0
                applied.append(dict(f, ok=True))
                continue
            applied.append(await asyncio.to_thread(
                _faults.apply, self.store, f))
        await self._heartbeat_once()
        return web.json_response({"applied": applied})

    async def handle_ec_shard_read(self, req: web.Request) -> web.Response:
        if self._fault_delay_shard_read > 0:
            await asyncio.sleep(self._fault_delay_shard_read)
        q = req.query
        vid, sid = int(q["volume"]), int(q["shard"])
        offset, size = int(q["offset"]), int(q["size"])
        ev = self.store.get_ec_volume(vid)
        if ev is None:
            return web.json_response({"error": "not mounted"}, status=404)
        data = ev._read_local(sid, offset, size)
        if data is None:
            return web.json_response({"error": "shard not local"}, status=404)
        return web.Response(body=data,
                            content_type="application/octet-stream")

    async def handle_ec_partial(self, req: web.Request) -> web.Response:
        """Reduced-read repair helper hop: compute the XOR-combinable
        partial product coeff @ local_shard_ranges over GF(2^8) (through
        the same ops/dispatch codec seam as encode) and return the raw
        [f, size] bytes.  A rebuilder pulling partials from d helpers
        ships f x range per helper NODE instead of full survivor shards
        — the repair-bandwidth floor of the aggregated decode.
        Quarantined (scrub-verdicted) ranges read as unreadable, so a
        corrupt survivor can never leak into a rebuilt shard: the
        rebuilder re-plans around the 409."""
        if self._fault_delay_shard_read > 0:
            await asyncio.sleep(self._fault_delay_shard_read)
        import numpy as np
        try:
            body = await req.json()
            vid = int(body["volume"])
            sids = [int(s) for s in body["shards"]]
            offset, size = int(body["offset"]), int(body["size"])
            coeff = np.asarray(body["coeff"], dtype=np.uint8)
            # MSR regenerating repair addresses SUB-ROWS: shard ids are
            # virtual (file*alpha + row), offset/size in sub-row bytes.
            # alpha=1 (absent for rs/lrc rebuilders and old callers)
            # keeps the original whole-shard semantics.
            alpha = int(body.get("alpha", 1) or 1)
        except (KeyError, TypeError, ValueError):
            return web.json_response({"error": "bad partial request"},
                                     status=400)
        # len(sids) x size bounds the rows compute() stacks in memory:
        # the legitimate rebuilder never asks for more than its batch
        # size per hop, and without the shard-count cap (and duplicate
        # check) one malformed request could pread an unbounded
        # multiple of `size` and OOM the server.  With sub-packetization
        # the ids are virtual (up to n*alpha of them) and every file
        # read is size*alpha bytes — both caps scale accordingly.
        if not sids or alpha < 1 or alpha > 64 or \
                len(sids) > layout.TOTAL_SHARDS * max(1, alpha) or \
                len(set(sids)) != len(sids) or \
                size <= 0 or size * alpha > ec_files.DEFAULT_BATCH or \
                coeff.ndim != 2 or coeff.shape[1] != len(sids) or \
                coeff.shape[0] > max(layout.PARITY_SHARDS, len(sids)):
            return web.json_response({"error": "bad partial shape"},
                                     status=400)
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({"error": "no shards here"},
                                     status=404)
        ev = self.store.get_ec_volume(vid)

        def read_range(fsid: int, off: int, n: int) -> bytes | None:
            if ev is not None:
                # honors the quarantine: corrupt ranges read as None
                return ev._read_local(fsid, off, n)
            p = base + layout.to_ext(fsid)
            try:
                fd = os.open(p, os.O_RDONLY)
                try:
                    return os.pread(fd, n, off)
                finally:
                    os.close(fd)
            except OSError:
                return None

        def compute() -> bytes:
            rows = []
            if alpha > 1:
                # one pread + de-interleave per FILE, shared by its
                # alpha virtual sub-rows
                blocks: dict[int, np.ndarray] = {}
                for fsid in sorted({s // alpha for s in sids}):
                    data = read_range(fsid, offset * alpha, size * alpha)
                    if data is None or len(data) != size * alpha:
                        raise KeyError(fsid)
                    blocks[fsid] = np.frombuffer(
                        data, dtype=np.uint8).reshape(size, alpha)
                for sid in sids:
                    rows.append(np.ascontiguousarray(
                        blocks[sid // alpha][:, sid % alpha]))
            else:
                for sid in sids:
                    data = read_range(sid, offset, size)
                    if data is None or len(data) != size:
                        raise KeyError(sid)
                    rows.append(np.frombuffer(data, dtype=np.uint8))
            from seaweedfs_tpu.ops import dispatch
            codec = ec_files._get_codec()
            return dispatch.apply_matrix(codec, coeff,
                                         np.stack(rows)).tobytes()

        try:
            with trace.span("volume.ec_partial", vid=vid,
                            shards=",".join(map(str, sids)),
                            bytes=size * len(sids)):
                out = await asyncio.to_thread(compute)
        except KeyError as e:
            return web.json_response(
                {"error": f"shard {e.args[0]} unreadable or quarantined"},
                status=409)
        return web.Response(body=out,
                            content_type="application/octet-stream")

    def _partial_fetcher(self, vid: int, alpha: int = 1):
        """Client side of /admin/ec/partial for the reduced rebuild:
        runs on executor threads, so the trace context, traffic class,
        and deadline are captured HERE.  Rides the resilience layer —
        per-peer breakers, deadline-clamped socket timeouts — and maps
        every failure to regen.HelperDied so the rebuild re-plans with a
        substitute survivor instead of aborting."""
        import json as _json
        import urllib.error
        import urllib.request
        from seaweedfs_tpu.maintenance import faults as _faults
        from seaweedfs_tpu.ops import regen
        tctx = trace.current()
        flow_cls = netflow.current_class() or "repair"
        dl = resilience.deadline()

        def fetch(group, sids, coeff, offset, size) -> bytes:
            node = group.node
            breaker = resilience.breaker_for(node) \
                if resilience.breaker_enabled() else None
            if breaker is not None and not breaker.allow():
                raise regen.HelperDied(node, tuple(sids))
            try:
                if _faults.NET_ACTIVE:
                    lat = _faults.check_net("volume", node)
                    if lat > 0:
                        time.sleep(lat)
            except OSError as e:
                raise regen.HelperDied(node, tuple(sids)) from e
            tmo = 60.0
            if dl is not None:
                tmo = min(tmo, dl - time.monotonic())
                if tmo <= 0.01:
                    raise regen.HelperDied(node, tuple(sids))
            payload = _json.dumps({
                "volume": vid, "shards": list(sids),
                "coeff": coeff.tolist(), "offset": offset,
                "size": size,
                **({"alpha": alpha} if alpha > 1 else {})}).encode()
            try:
                with trace.span("repair.partial_fetch", parent=tctx,
                                vid=vid, peer=node,
                                shards=",".join(map(str, sids)),
                                bytes=coeff.shape[0] * size,
                                locality=group.locality) as sp:
                    r = urllib.request.Request(
                        f"{_tls_scheme()}://{node}/admin/ec/partial",
                        data=payload,
                        headers={"Content-Type": "application/json"})
                    hdr_ctx = sp.trace or tctx
                    if hdr_ctx is not None:
                        r.add_header(trace.TRACE_HEADER,
                                     trace.format_header(hdr_ctx))
                    r.add_header(netflow.CLASS_HEADER, flow_cls)
                    r.add_header(netflow.ROLE_HEADER, "volume")
                    if dl is not None:
                        r.add_header(
                            resilience.DEADLINE_HEADER,
                            str(max(1, int((dl - time.monotonic())
                                           * 1000))))
                    with urllib.request.urlopen(r, timeout=tmo) as rr:
                        data = rr.read()
            except urllib.error.HTTPError as e:
                # the peer ANSWERED (quarantined survivor, shard moved):
                # a content miss, not a transport failure — re-plan
                # without this helper, but don't ding its breaker
                if breaker is not None:
                    breaker.record(True)
                raise regen.HelperDied(node, tuple(sids)) from e
            except OSError as e:
                if breaker is not None and \
                        (dl is None or dl - time.monotonic() > 0.05):
                    breaker.record(False)
                raise regen.HelperDied(node, tuple(sids)) from e
            if breaker is not None:
                breaker.record(True)
            netflow.account("recv", flow_cls, "volume", len(data))
            metrics.REPAIR_BYTES.labels(
                _topo_locality_name(group.locality)).inc(len(data))
            return data

        return fetch

    async def handle_ec_probe_read(self, req: web.Request) -> web.Response:
        """Canary degraded-read probe (stats/canary.py): read one REAL
        needle from an EC volume with one present shard deliberately
        skipped, forcing the reconstruction path end to end.  Read-only;
        returns the byte count and which shard was withheld."""
        try:
            vid = int(req.query.get("volume", "0"))
        except ValueError:
            return web.json_response({"error": "bad volume"}, status=400)
        ev = self.store.get_ec_volume(vid)
        if ev is None:
            return web.json_response({"error": "not mounted"}, status=404)
        nid = next((int(i) for i, sz in zip(ev.ids, ev.sizes)
                    if t.size_is_valid(int(sz))), None)
        if nid is None:
            return web.json_response({"error": "no needles"}, status=404)
        # withhold a shard the needle's data actually LIVES on — skipping
        # an unplanned shard would serve the read without ever touching
        # the decode path, and the probe exists to exercise exactly that.
        # skip_shards blocks the remote reader too, so any planned shard
        # forces reconstruction whether or not it is local.
        try:
            dat_off, size = ev.find_needle(nid)
            planned = sorted({sid for sid, _off, _n in ev.locate(
                dat_off, t.actual_size(size, ev.version))})
        except KeyError:
            planned = []
        if not planned:
            return web.json_response({"error": "no needles"}, status=404)
        skip = next((s for s in planned if s in ev.shards), planned[0])
        reader = self._shard_reader(vid)
        try:
            with trace.span("volume.probe_read", vid=vid, skip=skip):
                n = await asyncio.to_thread(
                    ev.read_needle, nid, reader,
                    skip_shards=frozenset({skip}))
        except (KeyError, IOError, ValueError) as e:
            return web.json_response(
                {"error": f"degraded probe read failed: {e}"}, status=503)
        return web.json_response({"needle": f"{nid:x}",
                                  "bytes": len(n.data),
                                  "skipped_shard": skip})

    async def handle_ec_recode(self, req: web.Request) -> web.Response:
        """Re-encode an EC volume under a DIFFERENT codec, in place: the
        autopilot codec_select actuator.  Decodes the stripe back to a
        temp .dat from the local shard set (regenerating any missing
        data shard first), re-encodes under the target codec —
        write_ec_files commits each shard tmp+rename and rewrites .vif
        with the new tag, so a crash mid-recode leaves either the old
        set or the new set, never a hybrid — then retires shard files
        past the new geometry.  Needs >= k_old shards locally; remnant
        shards on OTHER nodes are the caller's to retire (the autopilot
        does, exactly like tiering_promote)."""
        body = await req.json()
        try:
            vid = int(body["volume"])
        except (KeyError, TypeError, ValueError):
            return web.json_response({"error": "bad volume"}, status=400)
        from seaweedfs_tpu.ops import codecs as _codecs
        to = _codecs.parse_tag(body.get("codec") or _codecs.default_tag())
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({"error": "no shards here"}, status=404)
        old = _codecs.parse_tag((ec_files.read_vif(base) or {}).get("codec"))
        if old.tag == to.tag:
            return web.json_response({"codec": to.tag, "unchanged": True})
        if self._ec_jobs.get(vid, {}).get("state") == "running":
            return web.json_response({"error": "ec job already running"},
                                     status=409)
        present = [i for i in range(old.n)
                   if os.path.exists(base + layout.to_ext(i))]
        if len(present) < old.k:
            return web.json_response(
                {"error": f"recode needs {old.k} local shards, "
                          f"have {len(present)}"}, status=409)
        stages: dict = {}
        job = {"state": "running", "kind": "recode", "bytes_done": 0,
               "total": 0, "cancel": False, "error": None,
               "started": time.time(), "stages": stages,
               "from": old.tag, "codec": to.tag}
        self._ec_jobs[vid] = job
        tmp_dat = base + ".dat.recode"

        def work():
            if any(i not in present for i in range(old.k)):
                ec_files.rebuild_ec_files(base, codec_tag=old.tag)
            dat_size = ec_files.find_dat_file_size(base)
            job["total"] = dat_size
            # the blocks are the volume's: the new set keeps the old one's
            large, small = ec_files.volume_blocks(base)
            ec_files.write_dat_file(base, dat_size, out_path=tmp_dat)
            ec_files.write_ec_files(
                base, dat_path=tmp_dat, large_block=large,
                small_block=small,
                progress=lambda n: job.__setitem__("bytes_done", n),
                cancel=lambda: job["cancel"],
                stats=stages, codec_tag=to.tag)
            # shard files past the new geometry are stale ciphertext of
            # the OLD code — fsck would count them against the wrong
            # spec, and a later rebuild could mix matrices
            for i in range(to.n, max(old.n, to.n)):
                try:
                    os.remove(base + layout.to_ext(i))
                except OSError:
                    pass

        try:
            await asyncio.to_thread(work)
        except ec_files.EncodeCancelled:
            job["state"] = "cancelled"
            return web.json_response({"error": "cancelled"}, status=409)
        except Exception as e:
            job["state"] = "failed"
            job["error"] = str(e)
            refused = isinstance(e, _codecs.CodecUnsupported)
            return web.json_response({"error": str(e)},
                                     status=400 if refused else 500)
        finally:
            try:
                os.remove(tmp_dat)
            except OSError:
                pass
        # remount so the served spec matches the new shard set
        loc = next(l for l in self.store.locations
                   if base.startswith(l.directory))
        ev = loc.ec_volumes.pop(vid, None)
        if ev is not None:
            ev.close()
        loc.ec_volumes[vid] = ecv.EcVolume(base)
        job["state"] = "done"
        job["bytes_done"] = job["total"]
        await self._heartbeat_once()
        return web.json_response(
            {"codec": to.tag, "from": old.tag,
             "shards": loc.ec_volumes[vid].shard_ids()})

    async def handle_ec_to_volume(self, req: web.Request) -> web.Response:
        """VolumeEcShardsToVolume (volume_grpc_erasure_coding.go:407):
        decode local data shards back into a normal volume."""
        body = await req.json()
        vid = body["volume"]
        collection = body.get("collection", "")
        base = self._ec_base(vid)
        if base is None:
            return web.json_response({"error": "no shards here"}, status=404)
        from seaweedfs_tpu.ops import codecs as _codecs
        spec = _codecs.parse_tag((ec_files.read_vif(base) or {}).get("codec"))
        missing = [i for i in range(spec.k)
                   if not os.path.exists(base + layout.to_ext(i))]
        def decode():
            if missing:
                ec_files.rebuild_ec_files(base, codec_tag=spec.tag)
            dat_size = ec_files.find_dat_file_size(base)
            ec_files.write_dat_file(base, dat_size)
            ec_files.write_idx_from_ecx(base + ".ecx")
        await asyncio.to_thread(decode)
        # mount as a normal volume
        loc = next(l for l in self.store.locations if base.startswith(l.directory))
        from seaweedfs_tpu.storage.volume import Volume
        loc.volumes[vid] = Volume(loc.directory, collection, vid)
        loc.collections[vid] = collection
        await self._heartbeat_once()
        return web.json_response({})
