"""Tests for data types, config, persister, tranquilizer, background runner."""

import asyncio

import pytest

from garage_tpu.utils import background, config, data, migrate
from garage_tpu.utils.persister import Persister, PersisterShared


def test_hashes():
    assert len(data.sha256sum(b"hello")) == 32
    assert len(data.blake2sum(b"hello")) == 32
    assert data.blake2sum(b"a") != data.blake2sum(b"b")
    assert isinstance(data.fasthash(b"x"), int)
    u = data.gen_uuid()
    assert len(u) == 32
    assert data.hash_of_hex(data.hex_of(u)) == u


def test_config_parse(tmp_path):
    p = tmp_path / "garage.toml"
    p.write_text("""
metadata_dir = "/tmp/meta"
data_dir = "/tmp/data"
replication_factor = 3
block_size = "1M"
db_engine = "sqlite"
rpc_bind_addr = "127.0.0.1:3901"
bootstrap_peers = ["127.0.0.1:3902"]

[s3_api]
api_bind_addr = "127.0.0.1:3900"
s3_region = "garage"

[tpu]
batch_blocks = 8
""")
    cfg = config.read_config(str(p))
    assert cfg.metadata_dir == "/tmp/meta"
    assert cfg.data_dir[0].path == "/tmp/data"
    assert cfg.replication_factor == 3
    assert cfg.block_size == 10**6
    assert cfg.s3_api_bind_addr == "127.0.0.1:3900"
    assert cfg.bootstrap_peers == ["127.0.0.1:3902"]
    assert cfg.tpu.batch_blocks == 8
    assert cfg.erasure_params is None


def test_config_multi_hdd_and_erasure(tmp_path):
    p = tmp_path / "g.toml"
    p.write_text("""
metadata_dir = "/tmp/meta"
erasure_coding = "4,2"
data_dir = [
  { path = "/mnt/hdd1", capacity = "1T" },
  { path = "/mnt/hdd2", capacity = "500G", read_only = false },
]
""")
    cfg = config.read_config(str(p))
    assert cfg.erasure_params == (4, 2)
    assert cfg.data_dir[0].capacity == 10**12
    assert cfg.data_dir[1].capacity == 5 * 10**11


@pytest.mark.parametrize("key", [
    "device_min_bytes", "device_min_items", "device_min_decode_bytes",
    "device_min_decode_items", "trial_max_items", "trial_items_cap",
    "trial_max_bytes", "batch_blocs"])
def test_config_refuses_a_tpu_key_it_does_not_have_by_name(tmp_path, key):
    """A [tpu] key the section lacks — the seven routing knobs that went
    with the feeder's router, or a typo — is refused with the section
    and the key named, not with a bare TypeError."""
    says = ("unknown key" if key == "batch_blocs"
            else "no longer routes by size or by trial")
    p = tmp_path / "g.toml"
    p.write_text(f'metadata_dir = "/tmp/meta"\n\n[tpu]\n'
                 f'batch_blocks = 8\n{key} = 4\n')
    with pytest.raises(ValueError) as e:
        config.read_config(str(p))
    assert f"[tpu] {key}" in str(e.value) and says in str(e.value)
    assert key not in {f.name for f in
                       config.dataclasses.fields(config.TpuConfig)}


class PVal(migrate.Migratable):
    VERSION_MARKER = b"GTpv1"

    def __init__(self, n):
        self.n = n

    def pack(self):
        return self.n

    @classmethod
    def unpack(cls, raw):
        return cls(raw)


def test_persister(tmp_path):
    p = Persister(str(tmp_path), "val", PVal)
    assert p.load() is None
    p.save(PVal(42))
    assert p.load().n == 42
    # PersisterShared: persists default, then updates
    ps = PersisterShared(str(tmp_path), "shared", PVal, PVal(1))
    assert ps.get().n == 1
    ps.update(lambda v: PVal(v.n + 1))
    ps2 = PersisterShared(str(tmp_path), "shared", PVal, PVal(99))
    assert ps2.get().n == 2  # loaded, not default


def test_background_runner_lifecycle():
    async def main():
        runner = background.BackgroundRunner()
        done = []

        class W(background.Worker):
            name = "test-worker"

            def __init__(self):
                self.steps = 0

            async def work(self):
                self.steps += 1
                done.append(self.steps)
                if self.steps >= 3:
                    return background.WState.DONE
                return background.WState.BUSY

        runner.spawn_worker(W())
        await asyncio.sleep(0.1)
        infos = runner.worker_info()
        assert len(infos) == 1
        await runner.shutdown()
        assert done == [1, 2, 3]

    asyncio.run(main())


def test_background_worker_error_backoff():
    async def main():
        runner = background.BackgroundRunner()

        class Bad(background.Worker):
            name = "bad"

            async def work(self):
                raise RuntimeError("boom")

        runner.spawn_worker(Bad())
        await asyncio.sleep(0.15)
        info = list(runner.worker_info().values())[0]
        assert info.errors >= 1
        assert "boom" in info.last_error
        await runner.shutdown()

    asyncio.run(main())


def test_lockfile_exclusive(tmp_path):
    """Server-vs-offline-maintenance exclusion: second acquire fails
    while held (in a child process: flock is per-open-file, so a
    same-process re-acquire through a fresh fd would succeed), then
    succeeds after release."""
    import subprocess
    import sys

    from garage_tpu.utils import lockfile

    d = str(tmp_path / "meta")
    fd = lockfile.acquire(d, "server")
    child = (
        "import sys; from garage_tpu.utils import lockfile\n"
        f"d = {d!r}\n"
        "try:\n"
        "    lockfile.acquire(d, 'repair-offline')\n"
        "except lockfile.AlreadyLocked as e:\n"
        "    assert 'server' in str(e); sys.exit(42)\n"
        "sys.exit(0)\n"
    )
    r = subprocess.run([sys.executable, "-c", child])
    assert r.returncode == 42  # refused while the 'server' holds it
    lockfile.release(fd)
    r2 = subprocess.run([sys.executable, "-c", child])
    assert r2.returncode == 0  # free after release


@pytest.mark.parametrize("items", [1, 50, 1200])
def test_background_busy_worker_yields_between_items(items):
    """A worker whose work() never suspends must not hold the loop
    until its backlog is empty: between two items everybody else gets
    a turn (a holder's resync backlog stalled every shard fetch of the
    node for 1-2 s, PR 37)."""

    async def main():
        runner = background.BackgroundRunner()
        turns = []

        class Backlog(background.Worker):
            name = "backlog"

            def __init__(self):
                self.left = items

            async def work(self):  # no await inside: never suspends
                self.left -= 1
                turns.append("w")
                return (background.WState.BUSY if self.left
                        else background.WState.DONE)

        async def other():
            while True:
                turns.append("o")
                await asyncio.sleep(0)

        t = asyncio.create_task(other())
        await asyncio.sleep(0)
        runner.spawn_worker(Backlog())
        while turns.count("w") < items:
            await asyncio.sleep(0)
        t.cancel()
        await runner.shutdown()
        first = turns.index("w")
        span = turns[first:len(turns) - turns[::-1].index("w")]
        assert span.count("w") == items
        # never two items back to back
        assert "ww" not in "".join(span)

    asyncio.run(main())
