"""Foundation-layer tests (reference test analogs: mtls 396 LoC, crypto 336,
calendar 182 — SURVEY §4)."""

import datetime as dt
import threading

import pytest

from pbs_plus_tpu.utils import calendar, crypto, safemap, validate


# --- calendar ------------------------------------------------------------

def test_calendar_keywords():
    t = dt.datetime(2026, 7, 28, 13, 45, 12)
    assert calendar.compute_next_event("hourly", t) == dt.datetime(2026, 7, 28, 14, 0, 0)
    assert calendar.compute_next_event("daily", t) == dt.datetime(2026, 7, 29, 0, 0, 0)
    assert calendar.compute_next_event("weekly", t) == dt.datetime(2026, 8, 3, 0, 0, 0)  # monday
    assert calendar.compute_next_event("monthly", t) == dt.datetime(2026, 8, 1, 0, 0, 0)


def test_calendar_time_expressions():
    t = dt.datetime(2026, 7, 28, 13, 45, 12)
    assert calendar.compute_next_event("21:00", t) == dt.datetime(2026, 7, 28, 21, 0, 0)
    assert calendar.compute_next_event("06:30", t) == dt.datetime(2026, 7, 29, 6, 30, 0)
    # every 15 minutes
    assert calendar.compute_next_event("*:0/15", t) == dt.datetime(2026, 7, 28, 14, 0, 0)
    nxt = calendar.compute_next_event("*:0/15", dt.datetime(2026, 7, 28, 13, 10, 0))
    assert nxt == dt.datetime(2026, 7, 28, 13, 15, 0)


def test_calendar_weekday():
    t = dt.datetime(2026, 7, 28, 13, 45, 12)  # tuesday
    assert calendar.compute_next_event("sat 03:00", t) == dt.datetime(2026, 8, 1, 3, 0, 0)
    assert calendar.compute_next_event("mon..fri 02:00", t) == dt.datetime(2026, 7, 29, 2, 0, 0)
    # same-day later time
    assert calendar.compute_next_event("tue 18:00", t) == dt.datetime(2026, 7, 28, 18, 0, 0)


def test_calendar_date_expressions():
    t = dt.datetime(2026, 7, 28, 13, 45, 12)
    assert calendar.compute_next_event("*-*-01 00:00:00", t) == dt.datetime(2026, 8, 1, 0, 0, 0)
    assert calendar.compute_next_event("*-12-25 08:00", t) == dt.datetime(2026, 12, 25, 8, 0, 0)


def test_calendar_step_from_value():
    # systemd: "a/N" == from a to field max step N — including N=1
    assert sorted(calendar.parse("8/1:00").hours) == list(range(8, 24))
    assert sorted(calendar.parse("8/2:00").hours) == list(range(8, 24, 2))


def test_calendar_matches_and_errors():
    ev = calendar.parse("mon..fri 02:30")
    assert ev.matches(dt.datetime(2026, 7, 29, 2, 30, 0))
    assert not ev.matches(dt.datetime(2026, 8, 1, 2, 30, 0))  # saturday
    for bad in ["", "99:99", "frob", "25:00", "*:*:*/0"]:
        with pytest.raises(calendar.CalendarError):
            calendar.parse(bad)


# --- crypto --------------------------------------------------------------

def test_seal_roundtrip(tmp_path):
    pytest.importorskip("cryptography")     # sealing needs AESGCM
    key = crypto.load_or_create_key(str(tmp_path / "k"))
    key2 = crypto.load_or_create_key(str(tmp_path / "k"))
    assert key == key2
    blob = crypto.seal(key, b"secret", aad=b"ctx")
    assert crypto.unseal(key, blob, aad=b"ctx") == b"secret"
    with pytest.raises(Exception):
        crypto.unseal(key, blob, aad=b"wrong")
    with pytest.raises(Exception):
        crypto.unseal(crypto.generate_key(), blob, aad=b"ctx")


# --- safemap -------------------------------------------------------------

def test_safemap_compound_ops():
    m = safemap.SafeMap()
    v, loaded = m.get_or_set("a", lambda: 1)
    assert (v, loaded) == (1, False)
    v, loaded = m.get_or_set("a", lambda: 2)
    assert (v, loaded) == (1, True)
    m.compute("a", lambda old: (old or 0) + 10)
    assert m.get("a") == 11
    m.compute("a", lambda old: None)
    assert "a" not in m

    # concurrent increments stay consistent
    m.set("n", 0)
    def bump():
        for _ in range(1000):
            m.compute("n", lambda old: old + 1)
    ts = [threading.Thread(target=bump) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert m.get("n") == 4000


# --- validate ------------------------------------------------------------

def test_validate_paths():
    assert validate.safe_rel_path("a/b/c.txt") == "a/b/c.txt"
    for bad in ["/abs", "a/../b", "a//b", ".", "a/./b", "nul\x00"]:
        with pytest.raises(validate.ValidationError):
            validate.safe_rel_path(bad)
    assert validate.hostname("node-1.example.com")
    with pytest.raises(validate.ValidationError):
        validate.hostname("-bad-")


def test_rotating_log_file(tmp_path):
    """Size-rotated JSON file logging (reference: lumberjack rotation)."""
    import json as _json

    from pbs_plus_tpu.utils.log import (
        L, add_rotating_file, remove_rotating_file)

    path = tmp_path / "srv.log"
    h = add_rotating_file(str(path), max_bytes=4000, backups=2)
    try:
        import uuid
        run_tag = uuid.uuid4().hex[:8]   # defeat the global log dedup
        for i in range(200):
            L.info("rotation line %s-%d with some padding payload",
                   run_tag, i)
        files = sorted(p.name for p in tmp_path.glob("srv.log*"))
        assert "srv.log" in files and len(files) >= 2   # rotated
        line = open(path).readlines()[-1]
        rec = _json.loads(line)
        assert rec["level"] == "INFO" and "rotation line" in rec["msg"]
    finally:
        remove_rotating_file(h)


# --- jaxenv: compile-cache placement and the host/device twin switch ------

def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper leaves everything to jax
    (which reads the variable itself) and sets no other place in code."""
    import jax

    from pbs_plus_tpu.utils import jaxenv
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxenv.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_place(monkeypatch):
    """Unset: one fixed directory inside the checkout — never a tmpdir, a
    pid or a timestamp (the path is part of what a cache hit needs) — set
    in jax AND in the environment children inherit."""
    import os

    import jax

    from pbs_plus_tpu.utils import jaxenv
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jaxenv.configure_compile_cache() == jaxenv.CACHE_DIR \
        == os.path.join(repo, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == jaxenv.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == jaxenv.CACHE_DIR


def test_pick_twin_counts_and_never_swallows(monkeypatch):
    """On the CPU backend every twin runs its host side and says so; a
    backend jax cannot initialise is the caller's exception — not a
    quiet host run, and not remembered as a decision."""
    import jax

    from pbs_plus_tpu.utils import jaxenv
    jaxenv.on_accelerator.cache_clear()
    monkeypatch.setattr(jaxenv, "twin_counts", {})
    try:
        assert jaxenv.pick_twin("t") is False
        assert jaxenv.pick_twin("t") is False
        assert jaxenv.twin_counts == {"t": {"device": 0, "host": 2}}

        def broken():
            raise RuntimeError("injected: no backend")
        jaxenv.on_accelerator.cache_clear()
        monkeypatch.setattr(jax, "default_backend", broken)
        with pytest.raises(RuntimeError, match="no backend"):
            jaxenv.pick_twin("t")
        assert jaxenv.twin_counts["t"] == {"device": 0, "host": 2}
        monkeypatch.undo()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert jaxenv.pick_twin("u") is True      # the failure did not stick
    finally:
        monkeypatch.undo()
        jaxenv.on_accelerator.cache_clear()
