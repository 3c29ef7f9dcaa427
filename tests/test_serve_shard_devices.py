"""``launch/serve.py --kv`` places a sharded fleet one shard per chip where
the host has a chip for every shard, and on the default device otherwise."""

import pytest

from repro.launch import serve


class _Built(Exception):
    pass


@pytest.fixture
def captured(monkeypatch):
    """Run ``serve.main`` up to the fleet's construction; return the
    ``devices`` it was given, with four devices on the host."""
    from repro.distributed import kvshard

    host = [object() for _ in range(4)]
    got = {}

    def fake_fleet(*args, **kw):
        got.update(kw)
        raise _Built

    monkeypatch.setattr(serve.jax, "devices", lambda: host)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(kvshard, "ShardedDPAStore", fake_fleet)

    def run(*argv):
        got.clear()
        with pytest.raises(_Built):
            serve.main(["--kv", "--n-keys", "2000", *argv])
        return got["devices"]

    return host, run


@pytest.mark.parametrize("partition", ["range", "hash"])
def test_one_shard_per_chip_when_the_host_has_enough(captured, partition):
    host, run = captured
    assert run("--partition", partition, "--shards", "4") == host
    assert run("--partition", partition, "--shards", "2") == host[:2]


def test_default_device_when_the_host_has_too_few(captured):
    _, run = captured
    assert run("--partition", "range", "--shards", "8") is None


def test_a_fleet_that_reshards_stays_unplaced(captured):
    # a placed fleet keeps its shard count; --reshard-to changes it
    _, run = captured
    assert run("--partition", "range", "--shards", "2", "--reshard-to", "4") is None
