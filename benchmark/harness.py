"""What every cell of the benchmark shares: the server child, HTTP, the
master's admin lock, the volume cache and the checks on what a run leaves
on disk.  Nothing here imports JAX or the program under test, and nothing
here knows a code: what a shard set is comes from the configuration's
`codec` block and the reference module it names (`reference_of`)."""

from __future__ import annotations

import concurrent.futures
import glob
import hashlib
import http.client
import importlib
import json
import os
import re
import select
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".cache")
WORK_DIR = os.path.join(BENCH, "work")
OUT_DIR = os.path.join(BENCH, "out")

MIB = 1024 * 1024
FILE_LIMIT = 2 ** 30 - MIB  # a chip machine may refuse a file past 1 GiB
CACHE_BUDGET = 8 * 2 ** 30  # bytes of volume cache kept in a checkout
NEEDLE_OVERHEAD = 64  # upper bound of a record's bytes beyond its data

T_START = time.time()  # process start, as near as this module can say


class BenchFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[bench {time.time() - T_START:7.2f}] {msg}", file=sys.stderr,
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reference_of(codec: dict):
    """The plain reference a configuration's `codec` block names: the
    module `benchmark/<reference>.py`, held to its own word on the tag."""
    name = codec["reference"]
    check(re.fullmatch(r"\w+", name) and
          os.path.exists(os.path.join(BENCH, name + ".py")),
          f"no reference module {name!r}: {BENCH}/{name}.py is missing")
    ref = importlib.import_module(name)
    have = (codec["data_shards"], codec["parity_shards"])
    says = ref.set_of(codec["tag"])
    check(says == have,
          f"codec block: {have[0]} + {have[1]} shards under the tag "
          f"{codec['tag']!r}, of which {name}.py says {says}")
    return ref


def kernel_table() -> dict:
    """kernels.json, with every kernel that is a file of its own,
    `kernels/<kernel>.json`, added to its `kernels` by the file's name."""
    table = load_json(os.path.join(BENCH, "kernels.json"))
    for path in sorted(glob.glob(os.path.join(BENCH, "kernels", "*.json"))):
        spec = load_json(path)
        name = spec.pop("name", None)
        check(name == os.path.basename(path)[:-len(".json")] and
              name not in table["kernels"],
              f"{path} names the kernel {name!r}")
        table["kernels"][name] = spec
    return table


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_call(netloc: str, method: str, path: str, body=None,
              timeout: float = 120.0, conn=None):
    """One request -> (status, bytes).  `body` dicts go as JSON."""
    headers = {}
    if isinstance(body, dict):
        body = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    elif body is not None:
        headers["Content-Type"] = "application/octet-stream"
    own = conn is None
    if own:
        conn = http.client.HTTPConnection(netloc, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        if own:
            conn.close()


def http_json(netloc: str, method: str, path: str, body=None,
              timeout: float = 120.0, ok=(200, 201), conn=None) -> dict:
    status, raw = http_call(netloc, method, path, body, timeout, conn)
    check(status in ok, f"{method} http://{netloc}{path} -> {status}: "
          f"{raw[:600]!r}")
    return json.loads(raw) if raw else {}


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while chunk := f.read(8 * MIB):
            h.update(chunk)
    return h.hexdigest()


def sha256_files(paths: list[str]) -> list[str]:
    """hashlib releases the interpreter lock, so files hash side by side."""
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(sha256_file, paths))


def check_file_sizes(*roots: str) -> None:
    """No file a run causes may pass 2^30 - 1 MiB."""
    for root in roots:
        for d, _dirs, files in os.walk(root):
            for name in files:
                path = os.path.join(d, name)
                try:
                    size = os.path.getsize(path)
                except OSError:  # a temporary file that has gone already
                    continue
                check(size <= FILE_LIMIT, f"{path} is {size} bytes: past "
                      f"the {FILE_LIMIT} this benchmark allows any file")


def compile_cache() -> str:
    """Where the program keeps JAX's persistent compilation cache
    (seaweedfs_tpu/__init__.py): inside the checkout unless the machine
    says otherwise."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")


def fill(template, **kw):
    """Replace `{name}` in the strings of a JSON value; a string that is
    one placeholder alone takes the value's own type."""
    if isinstance(template, str):
        m = re.fullmatch(r"\{(\w+)\}", template)
        if m and m.group(1) in kw:
            return kw[m.group(1)]
        return re.sub(r"\{(\w+)\}",
                      lambda g: str(kw.get(g.group(1), g.group(0))), template)
    if isinstance(template, list):
        return [fill(x, **kw) for x in template]
    if isinstance(template, dict):
        return {k: fill(v, **kw) for k, v in template.items()}
    return template


def post_steps(srv, steps: list[dict], vids: list[int], **names) -> None:
    """The untimed POSTs to the volume server that a data file lists.  A
    step has `path` and `body` (`{vid}`, `{vids}` and `names` filled in),
    runs once or, with `each_volume`, once a volume, and with
    `expect_applied` holds /admin/faults to that many faults, all ok."""
    for step in steps:
        for vid in vids if step.get("each_volume") else vids[:1]:
            r = http_json(srv.volume, "POST", step["path"],
                          fill(step["body"], vid=vid, vids=vids, **names),
                          timeout=600)
            if "expect_applied" in step:
                applied = r.get("applied", [])
                check(len(applied) == step["expect_applied"] and
                      all(a.get("ok") for a in applied),
                      f"{step['path']}: {r}")


def perf_moved_bytes(perf: dict, kernels=None) -> float:
    """Bytes sent to and brought back from the device so far, by the h2d
    and d2h rows of /perf -> roofline.rows (of `kernels`, or of all)."""
    return sum(row["gbytes"] * 1e9 for row in perf["roofline"]["rows"]
               if row["backend"] == "device" and
               row["resource"] in ("h2d", "d2h") and
               (not kernels or row["kernel"] in kernels))


# -- the server child ---------------------------------------------------------

class Server:
    """The configuration's server, started through serve.py with an
    environment built here, not inherited: nothing that selects a codec,
    a tile or a platform may leak in from the shell that started the run
    (machine plumbing such as PATH and the TPU runtime's own variables
    passes through, and so does JAX_COMPILATION_CACHE_DIR)."""

    def __init__(self, config: dict, work: str, rehearsal: bool,
                 trace: bool):
        self.work = work
        self.data_dir = os.path.join(work, "data")
        self.log_path = os.path.join(work, "server.log")
        self.master = f"127.0.0.1:{free_port()}"
        self.volume = f"127.0.0.1:{free_port()}"
        self.filer_port = free_port()
        self.proc: subprocess.Popen | None = None
        self._reply = None
        drop = config["env_drop"]
        env = {k: v for k, v in os.environ.items()
               if k not in drop["names"] and
               not k.startswith(tuple(drop["prefixes"]))}
        groups = [config["env"]]
        if rehearsal:
            groups.append(config["rehearsal"]["env"])
        if trace:
            groups.append(config["env_traced"])
        for group in groups:
            for name, spec in group.items():
                env[name] = fill(spec["value"], work=work)
        self.env = env
        self.argv = fill(config["server_argv"], data=self.data_dir,
                         master_port=self.master.rpartition(":")[2],
                         volume_port=self.volume.rpartition(":")[2],
                         filer_port=self.filer_port)

    def start(self) -> None:
        os.makedirs(self.data_dir, exist_ok=True)
        r, w = os.pipe()
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(BENCH, "serve.py"),
                 "--reply-fd", str(w), "--", *map(str, self.argv)],
                cwd=ROOT, env=self.env, stdin=subprocess.PIPE,
                stdout=log, stderr=subprocess.STDOUT, pass_fds=[w],
                start_new_session=True)
        os.close(w)
        self._reply = os.fdopen(r, "r")
        deadline = time.time() + 180
        while time.time() < deadline:
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode} at start-up")
            try:
                st = http_json(self.master, "GET", "/dir/status", timeout=5)
                if st["Topology"]["nodes"]:  # the volume server joined
                    return
            except (OSError, BenchFailure, ValueError, KeyError):
                pass
            time.sleep(0.2)
        raise BenchFailure("server did not come up within 180 s")

    def control(self, command: str, timeout: float = 120.0) -> dict:
        """One command to serve.py's control thread -> its reply."""
        check(self.proc is not None and self.proc.poll() is None,
              "the server is gone")
        self.proc.stdin.write((command + "\n").encode())
        self.proc.stdin.flush()
        ready, _, _ = select.select([self._reply], [], [], timeout)
        check(ready, f"serve.py gave no reply to {command!r} in {timeout} s")
        reply = json.loads(self._reply.readline())
        check(reply.get("ok"), f"serve.py: {command!r} failed: {reply}")
        return reply

    def stop(self) -> int | None:
        """SIGTERM by pid, wait; the process group is killed if it lingers
        (or if anything it started outlives it)."""
        p = self.proc
        if p is None:
            return None
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc = None
        for f in (p.stdin, self._reply):
            try:
                f.close()
            except OSError:
                pass
        return p.wait(30)

    def log_mark(self) -> int:
        return os.path.getsize(self.log_path)

    def log_between(self, start: int, end: int | None = None) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(start)
            raw = f.read() if end is None else f.read(end - start)
        return raw.decode(errors="replace")

    def log_tail(self, n: int = 60) -> str:
        try:
            text = self.log_between(max(0, self.log_mark() - 262144))
        except OSError:
            return ""
        noise = ("aiohttp.access", "Finished ", "Compiling jit(")
        lines = [ln for ln in text.splitlines()
                 if not any(x in ln for x in noise)]
        return "\n".join(lines[-n:])

    def base(self, collection: str, vid: int) -> str:
        return os.path.join(self.data_dir, f"{collection}_{vid}")

    def perf(self) -> dict:
        return http_json(self.volume, "GET", "/perf")


class AdminLock:
    """The master's admin lock, held over HTTP and renewed from a thread,
    as the shell's `lock; ec.*; unlock` holds it: it parks the repair
    planner (which would rebuild a removed shard within ~15 s) and the
    conversion scheduler."""

    def __init__(self, srv: Server):
        self.srv = srv
        self.token = http_json(srv.master, "POST", "/admin/lock",
                               {"owner": "benchmark"})["token"]
        self._stop = threading.Event()
        self.error: Exception | None = None
        self._thread = threading.Thread(target=self._renew, daemon=True,
                                        name="bench-lock")
        self._thread.start()

    def _renew(self) -> None:
        while not self._stop.wait(8):  # it lapses after 30 s
            try:
                http_json(self.srv.master, "POST", "/admin/renew_lock",
                          {"token": self.token}, timeout=20)
            except Exception as e:  # read by release()
                self.error = e
                return

    def release(self) -> None:
        self._stop.set()
        self._thread.join(30)
        check(self.error is None, f"the admin lock was lost: {self.error}")
        http_json(self.srv.master, "POST", "/admin/unlock",
                  {"token": self.token})


# -- volumes: loaded through the served write path once, then cached ------------

def _sample_digest(path: str) -> str:
    """sha256 over 64 blocks of 64 KiB at fixed places of a file: cheap
    enough for every run, and the reference comparison holds the rest."""
    size = os.path.getsize(path)
    h = hashlib.sha256(str(size).encode())
    with open(path, "rb") as f:
        for i in range(64):
            f.seek(max(0, (size - 65536) * i // 63))
            h.update(f.read(65536))
    return h.hexdigest()


def _manifest_digest(volumes: list[dict]) -> str:
    return hashlib.sha256(
        json.dumps(volumes, sort_keys=True).encode()).hexdigest()


def load_volume(srv: Server, collection: str, seal_at: int, lo: int,
                hi: int, rng) -> dict:
    """Grow one volume in `collection` and write needles of lo..hi bytes
    (log-uniform) into it; the first needle that takes its `.dat` to
    >= `seal_at` bytes is the last (an acknowledged write has left the
    server's buffers, so the file's size is exact).  One writer, one write
    in flight: needles land in the order drawn, so a seed fixes every
    offset in the `.dat` and with them the shapes a degraded read meets."""
    r = http_json(srv.master, "POST",
                  f"/vol/grow?collection={collection}&count=1")
    check(r.get("count") == 1, f"grow {collection}: {r}")
    needles: list[list] = []
    ratio = hi / lo
    vid = None
    mconn = http.client.HTTPConnection(srv.master, timeout=60)
    vconn = http.client.HTTPConnection(srv.volume, timeout=120)
    try:
        while True:
            data = rng.bytes(int(lo * ratio ** rng.random()))
            a = http_json(srv.master, "GET",
                          f"/dir/assign?collection={collection}", conn=mconn)
            check(a["url"] == srv.volume, f"assigned to {a['url']}")
            http_json(srv.volume, "POST", "/" + a["fid"], body=data,
                      ok=(201,), conn=vconn)
            needles.append([a["fid"], hashlib.sha256(data).hexdigest(),
                            len(data)])
            this = int(a["fid"].split(",")[0])
            check(vid in (None, this),
                  f"{collection}: a second volume, {this}, took a write")
            vid = this
            size = os.path.getsize(srv.base(collection, vid) + ".dat")
            if size >= seal_at:
                break
    finally:
        mconn.close()
        vconn.close()
    check(size < seal_at + hi + NEEDLE_OVERHEAD,
          f"{collection}_{vid}.dat is {size} bytes, sealed at {seal_at}")
    return {"collection": collection, "vid": vid, "needles": needles}


class VolumeCache:
    """`benchmark/.cache/<config>-seed<n>/`: the sealed `.dat` / `.idx` of
    a configuration's volumes, the sha256 of every acknowledged write and
    the reference's shard hashes, left by the first run of a seed in a
    checkout, with the `codec` block (the reference's name in it) they
    were computed under.  Later runs start the server on a copy, which is
    what a restarted volume server does.  An entry whose checksum does not
    match, or that was built under another block, is removed and rebuilt;
    the oldest entries go when the cache would pass CACHE_BUDGET."""

    def __init__(self, config_name: str, codec: dict, seed: int,
                 rehearsal: bool):
        tag = f"{config_name}-seed{seed}" + ("-rehearsal" if rehearsal else "")
        self.dir = os.path.join(CACHE_DIR, tag)
        self.manifest_path = os.path.join(self.dir, "manifest.json")
        self.codec = codec

    def lookup(self) -> list[dict] | None:
        if not os.path.exists(self.manifest_path):
            return None
        try:
            m = load_json(self.manifest_path)
            if m["codec"] != self.codec:
                say(f"volume cache {self.dir}: built under the codec block "
                    f"{m['codec']}, rebuilding")
                shutil.rmtree(self.dir, ignore_errors=True)
                return None
            ok = m["digest"] == _manifest_digest(m["volumes"])
            for v in m["volumes"] if ok else ():
                base = os.path.join(self.dir, f"{v['collection']}_{v['vid']}")
                ok = ok and \
                    os.path.getsize(base + ".dat") == v["dat_bytes"] and \
                    _sample_digest(base + ".dat") == v["dat_sample"] and \
                    sha256_file(base + ".idx") == v["idx_sha256"]
        except (OSError, ValueError, KeyError):
            ok = False
        if not ok:
            say(f"volume cache {self.dir}: checksum mismatch, rebuilding")
            shutil.rmtree(self.dir, ignore_errors=True)
            return None
        os.utime(self.manifest_path)  # least recently used goes first
        return m["volumes"]

    def restore(self, volumes: list[dict], data_dir: str) -> None:
        os.makedirs(data_dir, exist_ok=True)
        for v in volumes:
            name = f"{v['collection']}_{v['vid']}"
            for ext in (".dat", ".idx"):
                shutil.copyfile(os.path.join(self.dir, name + ext),
                                os.path.join(data_dir, name + ext))

    def store(self, volumes: list[dict], data_dir: str) -> None:
        need = sum(v["dat_bytes"] for v in volumes)
        self._evict(need)
        tmp = self.dir + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(tmp)
        for v in volumes:
            name = f"{v['collection']}_{v['vid']}"
            for ext in (".dat", ".idx"):
                shutil.copyfile(os.path.join(data_dir, name + ext),
                                os.path.join(tmp, name + ext))
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"digest": _manifest_digest(volumes),
                       "codec": self.codec, "volumes": volumes}, f)
        os.rename(tmp, self.dir)

    @staticmethod
    def _evict(need: int) -> None:
        entries = []
        for name in os.listdir(CACHE_DIR) if os.path.isdir(CACHE_DIR) else ():
            d = os.path.join(CACHE_DIR, name)
            size = sum(os.path.getsize(os.path.join(d, f))
                       for f in os.listdir(d))
            try:
                age = os.path.getmtime(os.path.join(d, "manifest.json"))
            except OSError:
                age = 0.0  # a half-written entry: first to go
            entries.append((age, size, d))
        total = sum(s for _, s, _ in entries)
        for _age, size, d in sorted(entries):
            if total + need <= CACHE_BUDGET:
                break
            shutil.rmtree(d, ignore_errors=True)
            total -= size


def describe_volume(srv: Server, loaded: dict, ref, codec: dict) -> dict:
    """Manifest entry of one sealed volume: sizes, checksums, the shard
    hashes of the reference `ref` under the block `codec`, and for every
    needle its fid, the sha256 and size acknowledged at write time, and
    where its record lies."""
    base = srv.base(loaded["collection"], loaded["vid"])
    idx = ref.read_idx(base + ".idx")
    check(len(idx) == len(loaded["needles"]),
          f"{base}.idx lists {len(idx)} needles, {len(loaded['needles'])} "
          f"writes were acknowledged")
    needles = []
    for fid, digest, size in loaded["needles"]:
        offset, body = idx[ref.needle_id_of(fid)]
        needles.append([fid, digest, size, offset, ref.record_length(body)])
    shards, shard_size = ref.reference_shards(codec, base + ".dat")
    check(len(shards) == ref.shard_count(codec),
          f"the reference gave {len(shards)} shard hashes for a set of "
          f"{ref.shard_count(codec)}")
    return {"collection": loaded["collection"], "vid": loaded["vid"],
            "dat_bytes": os.path.getsize(base + ".dat"),
            "dat_sample": _sample_digest(base + ".dat"),
            "idx_sha256": sha256_file(base + ".idx"),
            "shard_size": shard_size, "shards_sha256": shards,
            "needles": needles}


def compare_shards(base: str, volume: dict, only=None) -> list[str]:
    """-> what differs between the shard files at `base` and the
    reference's (empty when all agree): all of the set, which is as many
    files as the reference gave hashes, or `only` the listed."""
    ids = list(range(len(volume["shards_sha256"]))) if only is None else only
    paths = [f"{base}.ec{i:02d}" for i in ids]
    wrong = [f"{p} is missing" for p in paths if not os.path.exists(p)]
    if wrong:
        return wrong
    for i, p, digest in zip(ids, paths, sha256_files(paths)):
        if os.path.getsize(p) != volume["shard_size"]:
            wrong.append(f"{p}: {os.path.getsize(p)} bytes, reference "
                         f"{volume['shard_size']}")
        elif digest != volume["shards_sha256"][i]:
            wrong.append(f"{p} differs from the reference")
    return wrong


def read_needle(conn, fid: str) -> tuple[int, bytes]:
    conn.request("GET", "/" + fid)
    r = conn.getresponse()
    return r.status, r.read()


def needle_ok(status: int, body: bytes, needle: list) -> bool:
    return status == 200 and len(body) == needle[2] and \
        hashlib.sha256(body).hexdigest() == needle[1]


def make_rng(seed: int, *stream: int):
    return np.random.default_rng([seed, *stream])
