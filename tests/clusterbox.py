"""Cluster-in-a-box: N full Garage nodes in one event loop, built for
layout-transition experiments (ISSUE 6 / ROADMAP "cluster-in-a-box
simulation harness").

Every node is a REAL Garage composition root — tables, merkle trees,
syncers, resync workers, the lot — on the loopback transport
(net/local.py), so add-node / drain-node / kill-and-restart transitions
exercise exactly the code a TCP cluster runs: table anti-entropy moves
block_ref rows, ref triggers drive the block rebalance, the resync
backlog drains, and the gossiped ack/sync trackers converge. Used by
tests/test_resize.py and bench.py's bench_resize segment; scales to a
few dozen nodes in-process.

The harness adds only what a test needs on top of Garage itself:
node lifecycle (add / crash / restart with persisted state), a
foreground workload driver that records per-op latency and failures
(the "zero failed quorum ops" assertion), and convergence waits.
"""

from __future__ import annotations

import asyncio
import os
import random
import time
from typing import Optional

from garage_tpu.model.garage import Garage
from garage_tpu.net import LocalNetwork
from garage_tpu.rpc.layout import NodeRole, ResizeOrchestrator
from garage_tpu.utils.config import Config, DataDir, QosConfig
from garage_tpu.utils.data import gen_uuid


class BoxNode:
    """One node's handle: survives crash/restart cycles (the Garage
    object is replaced, the meta/data dirs persist)."""

    def __init__(self, index: int, root: str):
        self.index = index
        self.root = root
        self.garage: Optional[Garage] = None
        self.task: Optional[asyncio.Task] = None
        self.alive = False

    @property
    def id(self) -> bytes:
        return self.garage.system.id

    @property
    def system(self):
        return self.garage.system

    @property
    def manager(self):
        return self.garage.block_manager


class ClusterBox:
    def __init__(self, tmp_path, n: int = 4, rf: int = 3,
                 erasure: Optional[tuple[int, int]] = None,
                 storage: Optional[set[int]] = None,
                 db_engine: str = "memory",
                 governor: bool = False,
                 status_interval: float = 0.1,
                 ping_interval: float = 0.3,
                 resync_retry_delay: float = 0.25,
                 zones: Optional[list[str]] = None,
                 zone_redundancy=None,
                 block_size: int = 1 << 20):
        self.tmp = str(tmp_path)
        self.n = n
        self.rf = rf
        self.erasure = erasure
        self.storage = set(range(n)) if storage is None else set(storage)
        # zone topology (ISSUE 16): one zone name per node index, e.g.
        # ["z1","z1","z2","z2","z3","z3"]. Default: everyone in "z1",
        # which keeps every pre-zone test byte-identical in behavior.
        # zone_redundancy (int or "maximum") is staged with the first
        # layout when given; None leaves the layout default intact.
        if zones is not None and len(zones) != n:
            raise ValueError(f"zones has {len(zones)} entries for {n} nodes")
        self.zones = zones if zones is not None else ["z1"] * n
        self.zone_redundancy = zone_redundancy
        self.block_size = block_size  # 1 MiB is Config's own default
        self.db_engine = db_engine
        self.governor = governor
        self.status_interval = status_interval
        self.ping_interval = ping_interval
        self.resync_retry_delay = resync_retry_delay
        self.net = LocalNetwork()
        self.nodes: list[BoxNode] = []

    # ---- config / node construction ------------------------------------

    def _config(self, root: str) -> Config:
        return Config(
            metadata_dir=os.path.join(root, "meta"),
            data_dir=[DataDir(path=os.path.join(root, "data"))],
            db_engine=self.db_engine,
            replication_factor=self.rf,
            erasure_coding=("%d,%d" % self.erasure
                            if self.erasure else None),
            qos=QosConfig(governor=self.governor,
                          governor_interval=0.5,
                          # resize experiments: let resync sprint when
                          # foreground is quiet, yield hard when not
                          resync_tranquility_max=0.5),
            block_size=self.block_size,
        )

    def _boot(self, node: BoxNode) -> None:
        g = Garage(self._config(node.root), local_net=self.net,
                   status_interval=self.status_interval,
                   ping_interval=self.ping_interval)
        # chaos-friendly retry cadence: a fault-failed resync entry
        # must come back within the harness window, not in a minute
        g.block_manager.resync.retry_delay = self.resync_retry_delay
        node.garage = g
        node.task = asyncio.create_task(g.run())
        node.alive = True

    async def _join(self, node: BoxNode, seed: BoxNode) -> None:
        await node.garage.netapp.try_connect(
            seed.garage.netapp.public_addr, seed.id)
        node.system.peering.add_peer(
            seed.garage.netapp.public_addr, seed.id)

    # ---- lifecycle ------------------------------------------------------

    async def start(self) -> "ClusterBox":
        for i in range(self.n):
            node = BoxNode(i, os.path.join(self.tmp, f"node{i}"))
            os.makedirs(node.root, exist_ok=True)
            self.nodes.append(node)
            self._boot(node)
        for node in self.nodes[1:]:
            await self._join(node, self.nodes[0])
        await self.wait(lambda: all(
            len(nd.garage.netapp.conns) == self.n - 1
            for nd in self.nodes), 20, "initial mesh")
        lm = self.nodes[0].system.layout_manager
        for i, nd in enumerate(self.nodes):
            if i in self.storage:
                # default topology is one zone for everyone: with
                # zone_redundancy "maximum" a per-node-zone spread
                # forces every partition onto the single-node zones and
                # a newly added node in a full zone would get ZERO
                # partitions — resize experiments want capacity-driven
                # movement, not zone pinning. Zone drills pass zones=
                # (+ usually an explicit zone_redundancy) instead.
                lm.history.stage_role(
                    nd.id, NodeRole(zone=self.zones[i],
                                    capacity=1 << 30))
        if self.zone_redundancy is not None:
            lm.history.stage_parameters(self.zone_redundancy)
        lm.apply_staged(None)
        await self.wait(lambda: all(
            nd.system.layout_manager.history.current().version == 1
            for nd in self.nodes), 20, "layout v1")
        return self

    async def add_node(self) -> BoxNode:
        """A new empty node joins the mesh (no storage role yet — stage
        + apply is the caller's transition to drive)."""
        i = len(self.nodes)
        node = BoxNode(i, os.path.join(self.tmp, f"node{i}"))
        os.makedirs(node.root, exist_ok=True)
        self.nodes.append(node)
        self._boot(node)
        await self._join(node, self.live()[0])
        await self.wait(lambda: len(node.garage.netapp.conns) >= 1,
                        15, "new node joined")
        return node

    async def stop_node(self, node: BoxNode) -> None:
        """Crash: the process goes away (unregistered from the loopback
        net so RPCs to it fail like a dead TCP peer), persisted state
        stays on disk.

        Order matters: the transport dies FIRST. Garage.stop() closes
        the db before System.run's own teardown gets to the netapp, and
        cancelling the run task outright can skip netapp.shutdown()
        entirely — leaving a zombie node serving RPCs against a closed
        database while peers never see the links drop."""
        node.alive = False
        self.net.nodes.pop(node.id, None)
        await node.garage.netapp.shutdown()
        await node.garage.stop()
        if node.task is not None:
            try:
                await asyncio.wait_for(node.task, 10.0)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                node.task.cancel()
                await asyncio.gather(node.task, return_exceptions=True)

    async def restart_node(self, node: BoxNode) -> None:
        """Reboot from persisted state (node key, layout history with
        its ack/sync trackers, sqlite resync queue, block files)."""
        assert not node.alive
        self._boot(node)
        await self._join(node, self.live()[0])

    def live(self) -> list[BoxNode]:
        return [nd for nd in self.nodes if nd.alive]

    async def stop(self) -> None:
        # transports first, all nodes: stopping garages one by one
        # leaves the earlier ones' closed dbs serving RPCs from the
        # later ones (a flood of ProgrammingError teardown noise)
        for nd in self.live():
            await nd.garage.netapp.shutdown()
        for nd in self.live():
            await nd.garage.stop()
        for nd in self.nodes:
            if nd.task is not None:
                nd.task.cancel()
        await asyncio.gather(
            *(nd.task for nd in self.nodes if nd.task is not None),
            return_exceptions=True)

    # ---- transitions ----------------------------------------------------

    def orchestrator(self, node: Optional[BoxNode] = None) -> ResizeOrchestrator:
        return ResizeOrchestrator((node or self.nodes[0]).system)

    def resync_backlog(self) -> int:
        return sum(nd.manager.resync.queue_len() +
                   nd.manager.resync.errors_len()
                   for nd in self.live())

    # ---- waits ----------------------------------------------------------

    async def wait(self, cond, timeout: float, what: str = "condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            await asyncio.sleep(0.05)
        if not cond():
            raise AssertionError(f"timeout waiting for {what}")


class Workload:
    """Sustained foreground PUT/GET traffic against the coordinator
    node, with per-op latency capture and a hard failure ledger — the
    instrument behind 'zero failed quorum reads/writes mid-resize'."""

    def __init__(self, box: ClusterBox, obj_kib: int = 64,
                 period: float = 0.03, op_timeout: float = 30.0,
                 zipf: Optional[float] = None, zipf_seed: int = 1234):
        self.box = box
        self.obj_kib = obj_kib
        self.period = period
        self.op_timeout = op_timeout
        # Zipf-like GET skew (ISSUE 16 zone drill): with exponent s,
        # read index = floor(len * u**s) for u ~ U(0,1) — s=0/None is
        # the old round-robin, s>=3 concentrates reads on the oldest
        # few objects (the "hot set" the cache tier should own)
        self.zipf = zipf
        self._zrng = random.Random(zipf_seed)
        self.bucket_id = gen_uuid()
        self.stored: list[tuple[bytes, bytes]] = []  # (hash, data)
        self.put_lat: list[float] = []
        self.get_lat: list[float] = []
        self.failures: list[str] = []
        self.corrupt = 0
        self._stop = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._n = 0

    def start(self) -> "Workload":
        self._task = asyncio.create_task(self._run())
        return self

    async def _run(self) -> None:
        from test_model import put_object_like_api

        g0 = self.box.nodes[0].garage
        rng_payload = os.urandom(self.obj_kib << 10)
        while not self._stop.is_set():
            self._n += 1
            do_put = self._n % 2 == 1 or not self.stored
            t0 = time.perf_counter()
            try:
                if do_put:
                    # unique payload per object: content-addressed
                    # stores dedupe identical blocks, which would turn
                    # the workload into a no-op
                    data = (self._n.to_bytes(8, "big")
                            + rng_payload[8:])
                    _uuid, h = await asyncio.wait_for(
                        put_object_like_api(
                            g0, self.bucket_id, f"o{self._n}", data),
                        self.op_timeout)
                    self.stored.append((h, data))
                    self.put_lat.append(time.perf_counter() - t0)
                else:
                    if self.zipf:
                        idx = int(len(self.stored)
                                  * (self._zrng.random() ** self.zipf))
                        idx = min(idx, len(self.stored) - 1)
                    else:
                        idx = self._n % len(self.stored)
                    h, data = self.stored[idx]
                    got = await asyncio.wait_for(
                        g0.block_manager.rpc_get_block(
                            h, cacheable=False),
                        self.op_timeout)
                    self.get_lat.append(time.perf_counter() - t0)
                    if got != data:
                        self.corrupt += 1
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.failures.append(
                    f"{'put' if do_put else 'get'} #{self._n}: "
                    f"{type(e).__name__}: {e}")
            await asyncio.sleep(self.period)

    async def stop(self) -> dict:
        self._stop.set()
        if self._task is not None:
            await self._task
        return self.stats()

    async def wait_ops(self, puts: int, gets: int,
                       timeout: float = 60.0) -> None:
        """Block until the driver has completed at least `puts`/`gets`
        ops. The driver is strictly sequential, so under a loaded
        full-suite run a transition window alone may not fit a fixed op
        count — callers that need an exercise floor wait for it instead
        of asserting it post-hoc."""
        deadline = time.monotonic() + timeout
        while (len(self.put_lat) < puts or len(self.get_lat) < gets):
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"workload op floor not reached in {timeout}s: "
                    f"{self.stats()}")
            await asyncio.sleep(0.1)

    @staticmethod
    def _pctl(xs: list[float], q: float) -> Optional[float]:
        if not xs:
            return None
        s = sorted(xs)
        return s[min(len(s) - 1, int(q * len(s)))]

    def stats(self) -> dict:
        return {
            "puts": len(self.put_lat),
            "gets": len(self.get_lat),
            "failures": list(self.failures),
            "corrupt": self.corrupt,
            "put_p50_ms": _ms(self._pctl(self.put_lat, 0.5)),
            "put_p99_ms": _ms(self._pctl(self.put_lat, 0.99)),
            "get_p50_ms": _ms(self._pctl(self.get_lat, 0.5)),
            "get_p99_ms": _ms(self._pctl(self.get_lat, 0.99)),
        }


def _ms(v: Optional[float]) -> Optional[float]:
    return round(v * 1e3, 2) if v is not None else None
