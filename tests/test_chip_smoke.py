"""`chip_smoke.py` off the chip: its phase function passes every check at a
small size on the CPU, `main()` refuses the CPU, and the compile-cache helper
sets a directory only where the environment names none."""

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.mark.usefixtures("native_lib")
@pytest.mark.parametrize("shard_docs", [False, True], ids=["one_device", "doc_sharded"])
def test_served_phase_passes_every_check_at_8_rooms(shard_docs):
    said = []
    failures = chip_smoke.served_phase(
        n_docs=8,
        capacity=256,
        n_sessions=16,
        events_per_session=8,
        seed=0,
        shard_docs=shard_docs,
        exact_rooms=4,
        say=said.append,
    )
    assert failures == [], (failures, said)
    text = "\n".join(said)
    assert "8 room slots x capacity 256" in text
    assert "of 4 touched rooms 4 render the oracle's text and 4 answer" in text
    assert "byte-equal to the Python one in 4/4" in text
    assert "fast_recoveries 0" in text and "encode.demotions 0" in text
    # `--chips 4`: a room a device here, and the server says so itself
    sharded = "29 of 29 state planes span 8 devices; the gauge ingest.state_shards reads 8"
    assert (sharded in text) == shard_docs


def test_served_phase_reports_a_room_that_left_the_oracle(monkeypatch):
    """A wrong oracle stands in for a wrong device: the phase must fail and
    say which room, and that replayed alone the room is right."""
    real = chip_smoke._oracle_docs

    def skewed(scenario):
        docs = real(scenario)
        hot = docs["tenant0"]
        with hot.transact() as txn:
            hot.get_text("text").insert(txn, 0, "x")
        return docs

    monkeypatch.setattr(chip_smoke, "_oracle_docs", skewed)
    failures = chip_smoke.served_phase(
        n_docs=8, capacity=256, n_sessions=16, events_per_session=8,
        exact_rooms=4, say=lambda _line: None,
    )
    assert len(failures) == 1, failures
    assert "1 rooms render another text" in failures[0]
    assert "tenant0: replayed alone both lanes match" in failures[0]


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(last)
    assert result["ok"] is False
    assert result["device"]["platform"] == "cpu"


def test_compile_cache_dir_is_fixed_or_the_environments(monkeypatch, tmp_path):
    from ytpu.utils.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # untouched
        # a cached program must not hand a tree its predecessor's op names
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert enable_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)
