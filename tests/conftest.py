"""Shared fixtures and the failure-set diff helper.

NOTE: no XLA_FLAGS here — tests must see 1 CPU device (the 512-device
mesh is exclusively the dry-run's business).

Failure-set baseline tooling
----------------------------
"Tests no worse than seed" is a statement about failure SETS, not exit
codes. Two options make that mechanically checkable::

    pytest -q --write-failures=results/failures.txt   # record the set
    pytest -q --diff-baseline=results/failures.txt    # exit 0 iff no NEW
                                                      # failures vs the file

``--diff-baseline`` prints newly-failing and newly-fixed node ids and
rewrites the session exit status: green iff the current failure set is a
subset of the baseline.
"""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# make sure the arch registry is populated for every test module
import repro.configs  # noqa: F401

ALL_ARCHS = [
    "minicpm3-4b", "grok-1-314b", "deepseek-moe-16b", "hymba-1.5b",
    "stablelm-12b", "llava-next-34b", "whisper-tiny", "qwen3-8b",
    "llama3.2-1b", "rwkv6-1.6b",
]


# ------------------------------------------------- failure-set baseline ---
_FAILED: set = set()


def pytest_addoption(parser):
    g = parser.getgroup("baseline", "failure-set baseline tooling")
    g.addoption("--write-failures", metavar="PATH", default=None,
                help="write the run's failure set (one test id per line)")
    g.addoption("--diff-baseline", metavar="PATH", default=None,
                help="diff the failure set against a baseline file; the "
                     "session exits 0 iff there are no NEW failures")


def pytest_runtest_logreport(report):
    if report.failed:
        _FAILED.add(report.nodeid)


def _read_baseline(path) -> set:
    p = pathlib.Path(path)
    if not p.exists():
        return set()
    return {ln.strip() for ln in p.read_text().splitlines() if ln.strip()}


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    bp = config.getoption("--diff-baseline")
    if not bp:
        return
    baseline = _read_baseline(bp)
    new = sorted(_FAILED - baseline)
    fixed = sorted(baseline - _FAILED)
    tr = terminalreporter
    tr.section("failure-set diff vs baseline")
    tr.write_line(f"baseline: {len(baseline)} failing, "
                  f"current: {len(_FAILED)} failing")
    for nid in new:
        tr.write_line(f"NEW     {nid}")
    for nid in fixed:
        tr.write_line(f"FIXED   {nid}")
    tr.write_line("no worse than baseline" if not new
                  else f"{len(new)} NEW failure(s)")


def pytest_sessionfinish(session, exitstatus):
    wp = session.config.getoption("--write-failures")
    if wp:
        p = pathlib.Path(wp)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("".join(f"{nid}\n" for nid in sorted(_FAILED)))
    bp = session.config.getoption("--diff-baseline")
    if bp and session.exitstatus in (0, 1):
        baseline = _read_baseline(bp)
        session.exitstatus = 1 if (_FAILED - baseline) else 0


# ------------------------------------------------------------- fixtures ---
@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def lm_smoke_batch(cfg, b=2, s=64, key=None):
    """Batch dict for any backbone's smoke config."""
    key = jax.random.PRNGKey(7) if key is None else key
    k1, k2 = jax.random.split(key)
    batch = {
        "tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size,
                                     dtype=jnp.int32),
        "labels": jax.random.randint(k2, (b, s), 0, cfg.vocab_size,
                                     dtype=jnp.int32),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.arch_type == "vlm":
        batch["img_embeds"] = 0.02 * jax.random.normal(
            k1, (b, cfg.n_image_tokens, cfg.d_model), cfg.dt)
    if cfg.encoder_layers > 0:
        batch["frames"] = 0.02 * jax.random.normal(
            k1, (b, cfg.encoder_seq, cfg.d_model), cfg.dt)
    return batch
