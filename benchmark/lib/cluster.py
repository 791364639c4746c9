"""The deployment of a cell: N forked `garage_tpu.cli.server` processes
with real directories on disk, set up through the admin HTTP API.

After chip_smoke.py's `Cluster` (PR 21), made general over the
configuration file and moved from ~16 forked CLI calls to the admin
API. This parent never imports JAX: the node the configuration names
under `chip_node` must get the chip. That node runs
`GARAGE_TPU_DEVICE=require`; every other node carries
`[tpu] enable = false` and must never map a JAX library.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

RPC_SECRET = "00112233445566778899aabbccddeeff" * 2
ADMIN_TOKEN = "benchmark-admin-token"
BUCKET = "bench"


class Failed(Exception):
    """The run cannot give a result; the message says why."""


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return str(v)
    return json.dumps(str(v))


class Cluster:
    def __init__(self, root: str, work: str, config: dict, rehearse: bool,
                 trace: bool):
        self.root, self.work, self.config = root, work, config
        self.rehearse, self.trace = rehearse, trace
        self.n = int(config["nodes"])
        self.zones = list(config["zones"])
        self.chip_node = int(config["chip_node"])
        if len(self.zones) != self.n or not 1 <= self.chip_node <= self.n:
            raise Failed("configuration: zones/chip_node do not fit nodes")
        self.toml = dict(config["toml"])
        if rehearse:
            self.toml.update(config["rehearse"].get("toml", {}))
        self.ports = {i: {"rpc": free_port(), "s3": free_port(),
                          "adm": free_port()} for i in range(1, self.n + 1)}
        self.procs: dict[int, subprocess.Popen] = {}
        self.node_id: dict[int, str] = {}
        env = dict(os.environ, PYTHONPATH=root, PYTHONUNBUFFERED="1")
        for k in ("GARAGE_TPU_DEVICE", "GARAGE_TPU_DEVICE_BACKEND",
                  "GARAGE_TPU_TRACE", "GARAGE_TPU_PALLAS"):
            env.pop(k, None)
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.env = env
        self.ctl = os.path.join(work, "node.ctl")
        self.ack = os.path.join(work, "node.ack")
        self.span_file = os.path.join(work, "spans.jsonl")
        self._acks_read = 0

    # ---- files ---------------------------------------------------------

    def dir(self, i: int) -> str:
        return os.path.join(self.work, f"node{i}")

    def conf(self, i: int) -> str:
        return os.path.join(self.dir(i), "garage.toml")

    def node_keys(self) -> list[tuple[str, bytes]]:
        """(node id, private key) per node, ascending by id, so that
        node i has the i-th lowest id. The layout lists a partition's
        nodes in id order (PR 21 finding 9a), so this fixes which nodes
        hold data shards (1..k) and which parity: the deployment, which
        is the configuration's and not the seed's. With keys drawn from
        the seed the killed nodes held a data shard in some runs and a
        parity shard in others, and the degraded cell had two speeds."""
        from garage_tpu.net.netapp import node_key_from_bytes

        raws = [hashlib.sha256(
            f"benchmark-node/{self.config['name']}/{j}".encode()).digest()
            for j in range(self.n)]
        return sorted((node_key_from_bytes(r).public_key()
                       .public_bytes_raw().hex(), r) for r in raws)

    def write_configs(self) -> None:
        keys = self.node_keys()
        self.node_id = {i: keys[i - 1][0] for i in self.ports}
        os.mkfifo(self.ctl)
        for i, p in self.ports.items():
            d = self.dir(i)
            os.makedirs(os.path.join(d, "meta"))
            fd = os.open(os.path.join(d, "meta", "node_key"),
                         os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
            with os.fdopen(fd, "wb") as f:
                f.write(keys[i - 1][1])
            if i != self.chip_node:
                tpu = "[tpu]\nenable = false\n"
            elif self.rehearse:
                tpu = '[tpu]\nplatform = "cpu"\n'
            else:
                tpu = ""
            top = "".join(f"{k} = {_toml_value(v)}\n"
                          for k, v in self.toml.items())
            with open(self.conf(i), "w") as f:
                f.write(f'''metadata_dir = "{d}/meta"
data_dir = "{d}/data"
{top}rpc_bind_addr = "127.0.0.1:{p["rpc"]}"
rpc_public_addr = "127.0.0.1:{p["rpc"]}"
rpc_secret = "{RPC_SECRET}"

[s3_api]
api_bind_addr = "127.0.0.1:{p["s3"]}"
s3_region = "garage"
root_domain = ".s3.garage.test"

[admin]
api_bind_addr = "127.0.0.1:{p["adm"]}"
admin_token = "{ADMIN_TOKEN}"

{tpu}''')

    # ---- processes -----------------------------------------------------

    def start(self, i: int) -> None:
        env = dict(self.env)
        server = ["--config", self.conf(i)]
        if i == self.chip_node:
            env["GARAGE_TPU_DEVICE"] = "require"
            if self.trace:
                env["GARAGE_TPU_TRACE"] = self.span_file
            # through node_main.py: the same main(), plus the channel
            # that only the chip's owner can serve (trace, memory peak)
            argv = [sys.executable,
                    os.path.join(self.root, "benchmark", "lib", "node_main.py"),
                    "--ctl", self.ctl, "--ack", self.ack, "--"] + server
        else:
            argv = [sys.executable, "-m", "garage_tpu.cli.server"] + server
        with open(os.path.join(self.dir(i), "log"), "ab") as log:
            self.procs[i] = subprocess.Popen(
                argv, cwd=self.root, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self, i: int) -> bool:
        p = self.procs.get(i)
        return p is not None and p.poll() is None

    def log_tail(self, i: int, n: int = 25) -> str:
        try:
            with open(os.path.join(self.dir(i), "log"), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def wait_up(self, i: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.alive(i):
                raise Failed(f"node {i} exited at boot:\n{self.log_tail(i)}")
            try:
                self.http(i, "GET", "/health", token=False, timeout=2.0)
                return
            except urllib.error.HTTPError:
                return  # it answers; "unavailable" until the layout is in
            except OSError:
                time.sleep(0.2)
        raise Failed(f"node {i} not up in {timeout:.0f}s:\n{self.log_tail(i)}")

    def kill(self, i: int) -> None:
        p = self.procs[i]
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
        p.wait(timeout=30)

    def loads_jax(self, i: int) -> bool:
        """Whether node i's process has mapped a JAX/XLA library."""
        with open(f"/proc/{self.procs[i].pid}/maps") as f:
            maps = f.read()
        return any(s in maps for s in ("jaxlib", "libtpu", "xla_extension"))

    def stop_all(self) -> None:
        """SIGTERM every node, wait, SIGKILL what is left, wait again."""
        for p in self.procs.values():
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + 15
        for p in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait()

    # ---- admin API -----------------------------------------------------

    def http(self, i: int, method: str, path: str, body=None,
             token: bool = True, timeout: float = 30.0):
        rq = urllib.request.Request(
            f"http://127.0.0.1:{self.ports[i]['adm']}{path}", method=method,
            data=None if body is None else json.dumps(body).encode())
        if token:
            rq.add_header("authorization", f"Bearer {ADMIN_TOKEN}")
        if body is not None:
            rq.add_header("content-type", "application/json")
        with urllib.request.urlopen(rq, timeout=timeout) as r:
            raw = r.read()
        return json.loads(raw) if raw.strip() else None

    def admin(self, method: str, path: str, body=None):
        try:
            return self.http(self.chip_node, method, path, body)
        except urllib.error.HTTPError as e:
            raise Failed(f"admin {method} {path}: HTTP {e.code} "
                         f"{e.read()[:300]!r}") from None

    def connected(self) -> int:
        try:
            return int(self.http(self.chip_node, "GET", "/v1/health")
                       ["connectedNodes"])
        except (OSError, ValueError, KeyError, TypeError):
            return -1

    def wait_connected(self, want: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.connected() == want:
                return
            time.sleep(0.2)
        raise Failed(f"connectedNodes is {self.connected()}, wanted {want}")

    def bring_up(self) -> tuple[str, str]:
        """Nodes up, connected, layout applied, key and bucket made.
        -> (access key id, secret key)."""
        self.write_configs()
        for i in self.ports:
            self.start(i)
        for i in self.ports:
            # the chip's owner imports JAX and takes its device verdict
            # at boot; the others are up in a second or two
            self.wait_up(i, 240 if i == self.chip_node else 60)
        peers = [f"{self.node_id[i]}@127.0.0.1:{self.ports[i]['rpc']}"
                 for i in self.ports if i != self.chip_node]
        for r in self.admin("POST", "/v1/connect", peers):
            if not r.get("success"):
                raise Failed(f"connect failed: {r}")
        self.wait_connected(self.n, 60)
        self.admin("POST", "/v1/layout", [
            {"id": self.node_id[i], "zone": self.zones[i - 1],
             "capacity": self.config["capacity"], "tags": []}
            for i in self.ports])
        self.admin("POST", "/v1/layout/apply", {})
        key = self.admin("POST", "/v1/key", {"name": "benchmark"})
        bucket = self.admin("POST", "/v1/bucket", {"globalAlias": BUCKET})
        self.admin("POST", "/v1/bucket/allow", {
            "bucketId": bucket["id"], "accessKeyId": key["accessKeyId"],
            "permissions": {"read": True, "write": True, "owner": True}})
        return key["accessKeyId"], key["secretAccessKey"]

    # ---- the chip owner's control channel (node_main.py) ---------------

    def control(self, line: str, timeout: float = 60.0) -> dict:
        """Send one command to node_main.py, wait for its answer."""
        if not self.alive(self.chip_node):
            raise Failed("the chip's node is gone:\n"
                         + self.log_tail(self.chip_node))
        deadline = time.monotonic() + timeout
        while True:
            try:
                # ENXIO while the reader is between two reads of the FIFO
                fd = os.open(self.ctl, os.O_WRONLY | os.O_NONBLOCK)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise Failed(f"node control {line!r}: nobody reads "
                                 f"{self.ctl}") from None
                time.sleep(0.01)
        try:
            os.write(fd, (line + "\n").encode())
        finally:
            os.close(fd)
        while time.monotonic() < deadline:
            try:
                with open(self.ack) as f:
                    acks = f.read().splitlines()
            except OSError:
                acks = []
            if len(acks) > self._acks_read:
                self._acks_read = len(acks)
                res = json.loads(acks[-1])
                if not res.get("ok"):
                    raise Failed(f"node control {line!r}: {res}")
                return res
            time.sleep(0.02)
        raise Failed(f"node control {line!r}: no answer in {timeout:.0f}s")
