#!/usr/bin/env python3
"""chip_smoke.py — the served EC path, end to end, on the chip.

Starts the all-in-one server (`python -m seaweedfs_tpu server -filer`) as a
child with `JAX_PLATFORMS=tpu` and the default codec selection, and drives
it the way a user would — HTTP and `python -m seaweedfs_tpu shell` — at the
size BASELINE.json calls its first configuration: one RS(10,4) volume of
1 GB (the source's 1 GB / 1 MB block sizes, so 96 small-block rows, each a
[10, 1 MiB] dispatch), filled with needles of 4 KiB - 1 MiB (log-uniform,
BASELINE.json configuration 4's object mix) drawn from --seed.  Large-block
rows are the benchmark's (`vol30g.encode`, a 30 GB volume's row structure
at 1/32; ROADMAP R9), not this script's.

No file past 1 GiB: a machine may refuse one (RLIMIT_FSIZE: EFBIG from the
append, a 500 to the client), so on every machine the volume is sealed at
the first needle that takes its `.dat` to >= 1,000,000,000 bytes, which
leaves it below 2^30 by more than 70 MB.  The script checks after every
phase that no file under its work directory or the compile cache passes
2^30 - 1 MiB.

This process never imports JAX: a chip belongs to one process, and that
process is the server.  What it knows about the device it reads from what
the server reports on `/perf`.  Every phase is fatal.

Phases:
  preflight  a one-needle volume through `ec.encode`: the first codec
             selection initialises the backend, so a host with no chip
             fails here, before any data is loaded
  load       one volume in its own collection, written through /dir/assign
             + volume POST; sha256 of every acknowledged write kept; a
             sample read back
  encode     `ec.encode` through the shell; all 14 shard files equal a
             reference computed here with numpy only (stripe by the
             layout's rule, times rs.get_code(10, 4).parity_matrix with
             ops/gf.gf_matmul)
  device     what the server says it ran on: platform tpu, the Pallas codec
             compiled (interpret false), encode_parity[device] rows and no
             encode_parity[host] row
  degraded   master admin lock held (parks the repair planner), data shards
             0 and 1 removed through /admin/faults, 96 small and large
             needles read back and checked; reconstruct[device] > 0
  rebuild    parity shard 12 removed as well (three lost: a 3-row decode
             matrix), `ec.rebuild` through the shell; rebuilt files equal
             the reference
  scrub      POST /admin/scrub: every window of the volume verified on the
             device, zero mismatches
  fleet      four more volumes of 256 MiB through
             POST /admin/ec/fleet_convert; shards equal the reference;
             fleet_encode[device] rows; on a multi-chip host the unit batch
             landed on every chip; the sets mounted, a sample read back
  fleet_rebuild
             two shards of one 256 MiB volume removed, `ec.rebuild` again:
             26 MiB shards end in a ragged [10, 10 MiB] batch, which the
             1 GB volume's 96 MiB shards (six whole 16 MiB batches) do not
  shutdown   SIGTERM the child by pid and wait for it

Output.  Standard output carries one line, the last thing written, and only
when every phase passed: a JSON object with exactly the keys of RESULT_KEYS
and DEVICE_KEYS below, the device as the server's JAX reports it.  A failed
run prints no result and exits nonzero.  Progress, phase walls, compile
counts and the tail of the server's log go to standard error; the full
detail is in `<workdir>/report.json`, next to `server.log` (the work
directory is `.smoke_work/` at the root of the checkout; its `data/` is
removed after a passing run).  No child writes to standard output: the
server logs to `server.log`, shells are captured.

`--rehearsal` is a tiny walk through the same phases on the CPU (XLA codec,
no Pallas) for a sandbox with no chip: its line says `"platform": "cpu"`
and proves nothing about the chip.
"""

import argparse
import concurrent.futures
import contextlib
import hashlib
import http.client
import json
import os
import re
import select
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

import numpy as np

try:
    import seaweedfs_tpu  # places JAX_COMPILATION_CACHE_DIR; no jax import
    from seaweedfs_tpu.models import rs  # numpy only
    from seaweedfs_tpu.ops import gf  # numpy only
except ImportError as e:
    sys.exit(f"chip_smoke.py runs from the root of a weedtpu checkout: {e}")

# The contract's result line: these keys, no more and no fewer.
RESULT_KEYS = ("ok", "device")
DEVICE_KEYS = ("platform", "kind", "count")

REPO = os.path.dirname(os.path.abspath(__file__))

K, M = 10, 4
MIB = 1024 * 1024
ROW = K * MIB  # one small-block row of the EC layout
SEAL_AT = 1_000_000_000  # the volume: BASELINE.json configuration 1
FLEET_SEAL_AT = 256 * MIB  # each of the four fleet volumes
FILE_LIMIT = 2 ** 30 - MIB  # no file the smoke causes may pass this
# upper bound of what a needle record holds beyond its data (header, sizes,
# flags, last-modified, checksum, timestamp, padding: 39-46 bytes today)
NEEDLE_OVERHEAD = 64
NEEDLE_LO, NEEDLE_HI = 4096, MIB
DEADLINE_S = 1000  # the driver allows 1200


def result_line(platform: str, kind: str, count: int) -> str:
    """The one line of standard output."""
    device = dict(zip(DEVICE_KEYS, (str(platform), str(kind), int(count))))
    return json.dumps(dict(zip(RESULT_KEYS, (True, device))))


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_call(netloc: str, method: str, path: str, body=None,
              timeout: float = 60.0, conn=None):
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
              timeout: float = 60.0, ok=(200, 201), conn=None) -> dict:
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


def largest_file(*roots: str) -> tuple[int, str]:
    """(size, path) of the largest file under the given directories."""
    best = (0, "")
    for root in roots:
        for d, _dirs, files in os.walk(root):
            for name in files:
                path = os.path.join(d, name)
                try:
                    best = max(best, (os.path.getsize(path), path))
                except OSError:  # a temporary file that has gone already
                    pass
    return best


# -- the plain reference ----------------------------------------------------

def reference_shards(dat_path: str) -> tuple[list[str], int]:
    """sha256 of each of the 14 shard files a `.dat` must encode to, and
    the shard file size — numpy only, independent of the code under test.

    The layout's rule for a volume under 10 x 1 GB: rows of ten 1 MiB
    blocks, the last row zero-padded; shard j is block j of every row."""
    size = os.path.getsize(dat_path)
    check(0 < size <= K * 1024 * MIB,
          f"{dat_path}: {size} bytes is outside the small-block layout")
    rows = -(-size // ROW)
    pm = rs.get_code(K, M).parity_matrix

    def one(r: int):
        with open(dat_path, "rb") as f:
            f.seek(r * ROW)
            raw = f.read(ROW)
        block = np.zeros(ROW, dtype=np.uint8)
        block[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        data = block.reshape(K, MIB)
        return data, gf.gf_matmul(pm, data)

    hashers = [hashlib.sha256() for _ in range(K + M)]
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as ex:
        for data, parity in ex.map(one, range(rows)):
            for h, block in zip(hashers, (*data, *parity)):
                h.update(block)
    return [h.hexdigest() for h in hashers], rows * MIB


# -- the server child -------------------------------------------------------

class Server:
    def __init__(self, work: str, rehearsal: bool):
        self.work = work
        self.data_dir = os.path.join(work, "data")
        self.log_path = os.path.join(work, "server.log")
        self.master = f"127.0.0.1:{free_port()}"
        self.volume = f"127.0.0.1:{free_port()}"
        self.filer_port = free_port()
        self.proc: subprocess.Popen | None = None
        # the child's environment is built here, not inherited: nothing
        # that selects a codec, a tile or a platform may leak in from the
        # shell that started the smoke (machine plumbing such as PATH and
        # the TPU runtime's own variables passes through, and so does
        # JAX_COMPILATION_CACHE_DIR, which the package has set by now)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("WEEDTPU_") and k not in (
                   "JAX_PLATFORMS", "JAX_PLATFORM_NAME",
                   "JAX_ENABLE_COMPILATION_CACHE")}
        env.update(
            # with `tpu,cpu` or nothing a failed TPU init lets JAX carry
            # on on the CPU, and `auto` would quietly hand out a host codec
            JAX_PLATFORMS="cpu" if rehearsal else "tpu",
            JAX_LOG_COMPILES="1",  # compile counts per phase, from the log
            WEEDTPU_CANARY_INTERVAL="0",
            PYTHONUNBUFFERED="1")
        if rehearsal:
            # CPU `auto` is the native host codec; the XLA codec keeps the
            # rehearsal on the device code path of ops/dispatch
            env["WEEDTPU_EC_CODEC"] = "jax"
        self.env = env
        # a shell is a host process next to the chip's owner: host codec,
        # CPU platform, so it can never ask for the chip
        self.shell_env = dict(env, WEEDTPU_EC_CODEC="cpp",
                              JAX_PLATFORMS="cpu")

    def start(self) -> None:
        os.makedirs(self.data_dir)
        mport = self.master.rpartition(":")[2]
        vport = self.volume.rpartition(":")[2]
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu", "server",
                 "-dir", self.data_dir, "-filer", "-max", "16",
                 "-port", mport, "-volumePort", vport,
                 "-filerPort", str(self.filer_port)],
                cwd=REPO, env=self.env, stdin=subprocess.DEVNULL,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        deadline = time.time() + 180
        while time.time() < deadline:
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode} at start-up")
            try:
                st = http_json(self.master, "GET", "/dir/status", timeout=5)
                if st["Topology"]["nodes"]:  # the volume server joined
                    return
            except (OSError, SmokeFailure, ValueError, KeyError):
                pass
            time.sleep(0.5)
        raise SmokeFailure("server did not come up within 180 s")

    def stop(self) -> int | None:
        """SIGTERM by pid, wait; the process group is killed if it
        lingers (or if anything it started outlives it).
        -> the server's exit code."""
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
        return p.wait(30)

    def log_mark(self) -> int:
        return os.path.getsize(self.log_path)

    def compiles_since(self, mark: int) -> dict:
        """Programs JAX built (or fetched from its cache) since `mark`,
        from JAX_LOG_COMPILES."""
        with open(self.log_path, "rb") as f:
            f.seek(mark)
            text = f.read().decode(errors="replace")
        # JAX's own handler writes "WARNING:<date>:jax..." lines; the
        # server's root handler repeats each record in its own format
        found = re.findall(r"^WARNING:.*Finished XLA compilation of "
                           r"jit\((.*?)\) in ([0-9.]+) sec", text, re.M)
        by_name: dict[str, int] = {}
        for name, _ in found:
            by_name[name] = by_name.get(name, 0) + 1
        return {"programs": len(found),
                "xla_compile_s": round(sum(float(s) for _, s in found), 3),
                "by_name": by_name}

    def log_tail(self, n: int = 60) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(max(0, os.path.getsize(self.log_path) - 262144))
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return ""
        noise = ("aiohttp.access", "Finished ", "Compiling jit(")
        lines = [ln for ln in lines if not any(x in ln for x in noise)]
        return "\n".join(lines[-n:])

    def log_grep(self, needle: str) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return next((ln.strip() for ln in f if needle in ln), "")
        except OSError:
            return ""

    def shell(self, script: str) -> str:
        """`python -m seaweedfs_tpu shell -c ...`, output captured."""
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.master, "-c", script],
            cwd=REPO, env=self.shell_env, stdin=subprocess.DEVNULL,
            capture_output=True, text=True, timeout=900)
        check(r.returncode == 0,
              f"shell -c {script!r} exited {r.returncode}:\n"
              f"{r.stdout[-1500:]}\n{r.stderr[-3000:]}")
        return r.stdout

    def base(self, collection: str, vid: int) -> str:
        return os.path.join(self.data_dir, f"{collection}_{vid}")

    def perf(self) -> dict:
        return http_json(self.volume, "GET", "/perf")


def kernel_rows(perf: dict, kernel: str) -> dict[str, int]:
    """{backend: calls} of one kernel's roofline rows on /perf."""
    out: dict[str, int] = {}
    for row in perf["roofline"]["rows"]:
        if row["kernel"] == kernel:
            out[row["backend"]] = max(out.get(row["backend"], 0),
                                      row["calls"])
    return out


def check_device_only(perf: dict, kernel: str, at_least: int = 1) -> int:
    rows = kernel_rows(perf, kernel)
    check(rows.get("device", 0) >= at_least,
          f"/perf shows {kernel}[device] calls = {rows.get('device', 0)}, "
          f"expected >= {at_least}: {rows}")
    check("host" not in rows,
          f"/perf shows a {kernel}[host] row: a host codec ran: {rows}")
    return rows["device"]


# -- writing and reading needles ---------------------------------------------

def fill_volume(srv: Server, collection: str, seal_at: int,
                rng) -> tuple[int, dict]:
    """Grow one volume in `collection` and write needles of 4 KiB - 1 MiB
    (log-uniform) into it; the first needle that takes its `.dat` to
    >= `seal_at` bytes is the last (an acknowledged write is flushed, so
    the file's size is exact), which leaves the `.dat` below seal_at +
    1 MiB + one record's overhead on every machine.  One writer, one
    write in flight: needles land in the order drawn, so a seed fixes
    every offset in the `.dat` and with them the shapes the degraded
    reads compile.
    -> (vid, {fid: (sha256, size)}) of the acknowledged writes."""
    r = http_json(srv.master, "POST",
                  f"/vol/grow?collection={collection}&count=1")
    check(r.get("count") == 1, f"grow {collection}: {r}")
    acked: dict[str, tuple[str, int]] = {}
    ratio = NEEDLE_HI / NEEDLE_LO
    mconn = http.client.HTTPConnection(srv.master, timeout=60)
    vconn = http.client.HTTPConnection(srv.volume, timeout=120)
    try:
        while True:
            data = rng.bytes(int(NEEDLE_LO * ratio ** rng.random()))
            a = http_json(srv.master, "GET",
                          f"/dir/assign?collection={collection}", conn=mconn)
            check(a["url"] == srv.volume, f"assigned to {a['url']}")
            http_json(srv.volume, "POST", "/" + a["fid"], body=data,
                      ok=(201,), conn=vconn)
            acked[a["fid"]] = (hashlib.sha256(data).hexdigest(), len(data))
            vid = int(a["fid"].split(",")[0])
            check(next(iter(acked)).startswith(f"{vid},"),
                  f"{collection}: a second volume, {vid}, took a write")
            if os.path.getsize(srv.base(collection, vid) + ".dat") >= seal_at:
                return vid, acked
    finally:
        mconn.close()
        vconn.close()


def read_back(srv: Server, acked: dict, fids: list[str],
              keepalive=None) -> None:
    conn = http.client.HTTPConnection(srv.volume, timeout=300)
    try:
        for fid in fids:
            if keepalive is not None:
                keepalive()
            status, body = http_call(srv.volume, "GET", "/" + fid,
                                     conn=conn)
            check(status == 200, f"GET {fid} -> {status}: {body[:300]!r}")
            digest, size = acked[fid]
            check(len(body) == size and
                  hashlib.sha256(body).hexdigest() == digest,
                  f"GET {fid}: {len(body)} bytes do not match the "
                  f"{size} bytes acknowledged at write time")
    finally:
        conn.close()


def compare_shards(base: str, want: list[str], size: int) -> None:
    for i in range(K + M):
        path = f"{base}.ec{i:02d}"
        check(os.path.exists(path), f"{path} is missing")
        check(os.path.getsize(path) == size,
              f"{path}: {os.path.getsize(path)} bytes, reference {size}")
        check(sha256_file(path) == want[i],
              f"{path} differs from the numpy reference")


class AdminLock:
    """The master's admin lock, held over HTTP and renewed: it parks the
    repair planner, which would otherwise heal a removed shard in ~15 s."""

    def __init__(self, srv: Server):
        self.srv = srv
        self.token = http_json(srv.master, "POST", "/admin/lock",
                               {"owner": "chip_smoke"})["token"]
        self.renewed = time.time()

    def keepalive(self) -> None:
        if time.time() - self.renewed > 8:  # it lapses after 30 s
            http_json(self.srv.master, "POST", "/admin/renew_lock",
                      {"token": self.token})
            self.renewed = time.time()

    def release(self) -> None:
        http_json(self.srv.master, "POST", "/admin/unlock",
                  {"token": self.token})


def remove_shards(srv: Server, vid: int, shards: list[int]) -> None:
    r = http_json(srv.volume, "POST", "/admin/faults", {"faults": [
        {"action": "delete_shard", "volume": vid, "shard": s}
        for s in shards]})
    check(len(r.get("applied", [])) == len(shards) and
          all(a.get("ok") for a in r["applied"]), f"delete_shard: {r}")


def shell_rebuild(srv: Server, lock: AdminLock, vid: int,
                  lost: list[int]) -> None:
    """`ec.rebuild` through the shell's REPL, once the master has heard
    (by heartbeat) that volume `vid` lacks the `lost` shards: the shell
    plans from the master's view.  The shell is started first and only
    then is our lock released, so its own `lock` follows within
    milliseconds and the repair planner gets no tick in between (it would
    rebuild the shards itself, through the same endpoint, and leave the
    shell nothing to do)."""
    deadline = time.time() + 60
    while True:
        lock.keepalive()
        r = http_json(srv.master, "GET", f"/dir/ec/lookup?volumeId={vid}",
                      ok=(200, 404))
        have = {int(s) for s in r.get("shards", {})}
        if have == set(range(K + M)) - set(lost):
            break
        check(time.time() < deadline, f"the master still lists shards "
              f"{sorted(have)} of volume {vid}; removed: {lost}")
        time.sleep(0.2)
    p = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "shell",
         "-master", srv.master],
        cwd=REPO, env=srv.shell_env, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    out = b""
    try:
        fd = p.stdout.fileno()
        deadline = time.time() + 120
        while not out.endswith(b"> "):
            lock.keepalive()
            check(time.time() < deadline and p.poll() is None,
                  f"the shell gave no prompt: {out[-800:]!r}")
            if select.select([fd], [], [], 1.0)[0]:
                out += os.read(fd, 65536)
        lock.release()
        rest, _ = p.communicate(b"lock\nec.rebuild\nunlock\nexit\n",
                                timeout=900)
        out += rest
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(30)
    text = out.decode(errors="replace")
    check(p.returncode == 0 and "error:" not in text and
          f"volume {vid}: rebuilt {lost}" in text,
          f"shell ec.rebuild of shards {lost} of volume {vid} "
          f"({p.returncode}):\n{text[-3000:]}")


# -- the run ------------------------------------------------------------------

def cache_entries(cache_dir: str) -> int:
    """Compiled programs in JAX's persistent cache directory."""
    n = 0
    for _root, _dirs, files in os.walk(cache_dir):
        n += sum(1 for f in files if not f.endswith("-atime"))
    return n


def run(args, srv: Server, report: dict, cache_dir: str) -> None:
    rng = np.random.default_rng(args.seed)
    small = args.rehearsal
    seal_at = 24 * MIB if small else SEAL_AT
    fleet_seal_at = 12 * MIB if small else FLEET_SEAL_AT
    sample = 16 if small else 48
    phases = report["phases"]

    @contextlib.contextmanager
    def phase(name: str):
        say(f"phase {name} ...")
        t0, mark = time.time(), srv.log_mark()
        yield
        size, path = largest_file(srv.work, cache_dir)
        check(size <= FILE_LIMIT,
              f"{path} is {size} bytes: past the {FILE_LIMIT} this run "
              f"allows any file")
        if size > report["largest_file"]["bytes"]:
            report["largest_file"] = {"bytes": size, "path": path}
        phases[name] = {"wall_s": round(time.time() - t0, 2),
                        "compiles": srv.compiles_since(mark)}
        c = phases[name]["compiles"]
        say(f"phase {name} passed in {phases[name]['wall_s']} s of wall; "
            f"the server built {c['programs']} programs "
            f"({c['xla_compile_s']} s)")

    # -- preflight: is there a chip, and does the codec compile ------------
    with phase("preflight"):
        pvid, _ = fill_volume(srv, "smokeprobe", 1, rng)
        pref, psize = reference_shards(srv.base("smokeprobe", pvid) + ".dat")
        srv.shell(f"lock; ec.encode -volumeId {pvid} "
                  f"-collection smokeprobe -force; unlock")
        compare_shards(srv.base("smokeprobe", pvid), pref, psize)
        blocks = srv.perf()["codecs"]
        check(blocks, "/perf carries no codec block after an encode")
        for b in blocks:
            check(small or (b.get("platform") == "tpu" and
                            b.get("codec") == "PallasRSCodec" and
                            b.get("interpret") is False),
                  f"the server did not encode with the compiled Pallas "
                  f"codec on a TPU: {b}")
        b = blocks[0]
        report["device"] = {"platform": b["platform"],
                            "kind": b["device_kind"],
                            "count": b["device_count"]}
        report["codec"] = {k: b.get(k) for k in
                           ("asked", "tag", "codec", "interpret", "tile")}
        say(f"server reports {report['device']} {report['codec']}")
    n_dev = report["device"]["count"]

    # -- load ----------------------------------------------------------------
    with phase("load"):
        vid, acked = fill_volume(srv, "smoke", seal_at, rng)
        base = srv.base("smoke", vid)
        dat_size = os.path.getsize(base + ".dat")
        check(seal_at <= dat_size < seal_at + NEEDLE_HI + NEEDLE_OVERHEAD,
              f"{base}.dat is {dat_size} bytes, sealed at {seal_at}")
        fids = sorted(acked)
        read_back(srv, acked,
                  [fids[i] for i in rng.choice(len(fids), 32, replace=False)])
        report["sizes"] = {"volume_dat_bytes": dat_size,
                           "needles": len(acked)}
        say(f"volume {vid}: {len(acked)} needles, .dat {dat_size} bytes")

    # -- encode --------------------------------------------------------------
    with phase("reference"):
        ref, shard_size = reference_shards(base + ".dat")
    rows = shard_size // MIB
    with phase("encode"):
        out = srv.shell(f"lock; ec.encode -volumeId {vid} -collection smoke "
                        f"-force; unlock")
        check(f"ec.encode {vid} done" in out, f"ec.encode said:\n{out}")
        compare_shards(base, ref, shard_size)

    # -- device proof ----------------------------------------------------------
    with phase("device"):
        perf = srv.perf()
        # one dispatch per unit of sixteen small-block rows (16 MiB of
        # every shard: ec_files._iter_spans), plus the preflight volume's
        check_device_only(perf, "encode_parity", -(-rows // 16) + 1)
        check(len(perf["codecs"]) == len(blocks),
              f"a second codec was resolved: {perf['codecs']}")

    # -- degraded read ---------------------------------------------------------
    with phase("degraded"):
        lock = AdminLock(srv)
        remove_shards(srv, vid, [0, 1])
        sizes = sorted(fids, key=lambda f: acked[f][1])
        third = len(sizes) // 3
        picks = [sizes[i] for i in rng.choice(third, sample, replace=False)]
        picks += [sizes[len(sizes) - 1 - i]
                  for i in rng.choice(third, sample, replace=False)]
        read_back(srv, acked, picks, keepalive=lock.keepalive)
        rec_calls = check_device_only(srv.perf(), "reconstruct")
        report["degraded"] = {"needles_read": len(picks),
                              "reconstruct_device_calls": rec_calls}

    # -- rebuild ---------------------------------------------------------------
    with phase("rebuild"):
        lock.keepalive()
        remove_shards(srv, vid, [12])
        shell_rebuild(srv, lock, vid, [0, 1, 12])
        compare_shards(base, ref, shard_size)
        check_device_only(srv.perf(), "reconstruct", rec_calls + 1)

    # -- scrub -----------------------------------------------------------------
    with phase("scrub"):
        # the operator's live retune: at the default 8 MB/s one pass over
        # 14 shard files of a 1 GB volume takes three minutes
        http_json(srv.volume, "POST", "/admin/scrub_rate", {"mbps": 4000})
        before = kernel_rows(srv.perf(), "encode_parity").get("device", 0)
        summary = http_json(srv.volume, "POST", "/admin/scrub", {},
                            timeout=900)
        for v, res in summary["volumes"].items():
            check("error" not in res, f"scrub of volume {v}: {res}")
        res = summary["volumes"][str(vid)]
        check(res["kind"] == "ec" and res["windows"] == rows and
              res["windows_skipped"] == 0 and res["corrupt"] == [],
              f"scrub of volume {vid} (want {rows} clean windows): {res}")
        check_device_only(srv.perf(), "encode_parity", before + rows)

    # -- fleet convert -----------------------------------------------------------
    with phase("fleet_load"):
        fleet = []
        for i in range(4):
            coll = f"smokefleet{i}"
            fvid, facked = fill_volume(srv, coll, fleet_seal_at, rng)
            fleet.append((coll, fvid))
            acked.update(facked)
        report["sizes"]["fleet_dat_bytes"] = [
            os.path.getsize(srv.base(c, v) + ".dat") for c, v in fleet]
    with phase("fleet_reference"):
        frefs = [reference_shards(srv.base(c, v) + ".dat")
                 for c, v in fleet]
    with phase("fleet"):
        r = http_json(srv.volume, "POST", "/admin/ec/fleet_convert",
                      {"volumes": [v for _, v in fleet]}, timeout=900)
        check(r.get("converted") == [v for _, v in fleet] and
              not r.get("skipped"), f"fleet_convert: {r}")
        for (c, v), (fref, fsize) in zip(fleet, frefs):
            compare_shards(srv.base(c, v), fref, fsize)
        perf = srv.perf()
        check_device_only(perf, "fleet_encode")
        # every phase before this one uses device 0 by design; fleet
        # conversion is the path that spreads over every chip of the host
        check(r.get("devices") == n_dev,
              f"fleet unit batches landed on {r.get('devices')} device(s), "
              f"the host has {n_dev}: {r}")
        report["fleet"] = {"devices": r["devices"], "units": r["units"],
                           "codecs": [b["codec"] for b in perf["codecs"]]}
        # seal as the master's conversion scheduler does after a batch:
        # mount the shard set, drop the .dat — reads now come from shards
        for _, v in fleet:
            for path in ("/admin/ec/mount", "/admin/volume/delete"):
                http_json(srv.volume, "POST", path, {"volume": v})
        ffids = sorted(f for f in acked if f not in fids)
        read_back(srv, acked, [ffids[i] for i in
                               rng.choice(len(ffids), 32, replace=False)])

    # -- rebuild with a ragged last batch ----------------------------------------
    with phase("fleet_rebuild"):
        # the 1 GB volume's 96 MiB shards are six whole [10, 16 MiB]
        # rebuild batches; a 256 MiB volume's 26 MiB shards end in a
        # ragged [10, 10 MiB] one (and two lost is a 2-row decode matrix)
        (c, v), (fref, fsize) = fleet[0], frefs[0]
        check(fsize % (16 * MIB), f"no ragged batch in {fsize}-byte shards")
        before = kernel_rows(srv.perf(), "reconstruct").get("device", 0)
        lock = AdminLock(srv)
        remove_shards(srv, v, [3, 11])
        shell_rebuild(srv, lock, v, [3, 11])
        compare_shards(srv.base(c, v), fref, fsize)
        check_device_only(srv.perf(), "reconstruct",
                          before + -(-fsize // (16 * MIB)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU walk-through; proves nothing about a chip")
    ap.add_argument("--workdir", default=os.path.join(REPO, ".smoke_work"),
                    help="scratch directory (wiped at start)")
    args = ap.parse_args()

    # standard output belongs to the result line alone: whatever else this
    # process or a child might write there goes to standard error
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    cache_dir = seaweedfs_tpu.COMPILE_CACHE_DIR

    if os.path.exists(args.workdir):
        shutil.rmtree(args.workdir)
    os.makedirs(args.workdir)
    report: dict = {
        "ok": False, "rehearsal": args.rehearsal, "seed": args.seed,
        "device": None, "codec": None,
        "compile_cache": {"dir": cache_dir,
                          "entries_before": cache_entries(cache_dir)},
        "largest_file": {"bytes": 0, "path": ""}, "phases": {},
        "note": "wall_s are wall seconds of this script's phases in one "
                "run (HTTP, disk and, where programs were built, "
                "compilation included); they are not metrics"}

    def on_signal(signum, frame):
        raise SmokeFailure(f"signal {signum} (the alarm is set to "
                           f"{DEADLINE_S} s)")
    signal.signal(signal.SIGALRM, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    signal.alarm(DEADLINE_S)

    srv = Server(args.workdir, args.rehearsal)
    t0 = time.time()
    try:
        srv.start()
        report["phases"]["start"] = {"wall_s": round(time.time() - t0, 2)}
        run(args, srv, report, cache_dir)
        report["ok"] = True
    except Exception as e:  # every failure, the alarm's included: exit 1
        traceback.print_exc()
        say("---- end of the server's log ----\n" + srv.log_tail())
        why = srv.log_grep("Unable to initialize backend")
        report["failure"] = f"{type(e).__name__}: {e}"
        say(f"FAILED: {e}" +
            (f"\nno accelerator for the server: {why}" if why else "") +
            f"\n(work directory kept: {args.workdir})")
    finally:
        signal.alarm(0)
        report["server_exit"] = srv.stop()
        report["compile_cache"]["entries_after"] = cache_entries(cache_dir)
        report["wall_s"] = round(time.time() - t0, 2)
        with open(os.path.join(args.workdir, "report.json"), "w") as f:
            json.dump(report, f, indent=1)
    if not report["ok"]:
        return 1
    shutil.rmtree(srv.data_dir)
    say(f"passed in {report['wall_s']} s of wall: "
        f"{json.dumps({k: v for k, v in report.items() if k != 'phases'})}")
    print(result_line(**report["device"]), file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
