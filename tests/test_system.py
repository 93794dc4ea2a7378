"""End-to-end system tests: the paper's full workload behind the public API,
plus a real dry-run cell executed in a subprocess (the 512-device path)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_paper_workload_end_to_end(rng):
    """bits -> conv encode -> BSC -> branch metrics -> fused Viterbi ->
    recovered bits, at the paper's sizes (12..60 coded bits) and at
    TPU-throughput batch."""
    from repro.configs.paper_viterbi import ARCH
    from repro.data.pipeline import ViterbiStream
    from repro.decode import CodecSpec, get_decoder
    from repro.decode.request import DecodeContext

    spec = CodecSpec(code=ARCH.code)
    for shape in ARCH.shapes[:5]:  # the paper's Fig. 3 sweep
        stream = ViterbiStream(ARCH.code, shape.n_info_bits, batch=8,
                               flip_prob=0.02)
        batch = stream(0)
        res = get_decoder("fused_packed")(spec, batch["bm_tables"], ctx=DecodeContext())
        ber = float((res.info_bits != batch["info_bits"]).mean())
        assert ber < 0.2, (shape.name, ber)


def test_trellis_expansion_count_matches_paper():
    """§V: Viterbi for 12 coded bits calls the expansion function 19 times;
    our full-sequence kernel runs exactly T=6 grid steps of batched ACS —
    the fused equivalent (4 states × 6 steps ≥ 19 active expansions)."""
    from repro.core import paper_expansion_calls

    assert paper_expansion_calls(12) == 19
    assert paper_expansion_calls(60) == 115


def test_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache sits at a fixed, git-ignored path inside the checkout.  The
    config is restored before any compile can initialize the cache."""
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache

    repo = Path(__file__).resolve().parent.parent
    assert DEFAULT_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """The multi-pod dry-run machinery works end to end: lower + compile a
    real cell on the 512-device (2,16,16) mesh in a fresh process."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm_350m",
         "--shape", "decode_32k", "--mesh", "multi", "--force"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=str(REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    cell = json.loads(
        (REPO / "benchmarks/results/dryrun/xlstm_350m--decode_32k--multi.json"
         ).read_text())
    assert cell["status"] == "ok"
    assert cell["chips"] == 512
    # fits per-chip HBM
    assert cell["memory_analysis"]["temp_size_in_bytes"] < 16 * 2 ** 30


def test_seqparallel_decode_on_mesh(mesh11, rng):
    from repro.decode import CodecSpec, decode

    spec = CodecSpec()
    bits = jax.random.bernoulli(rng, 0.5, (4, 62)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits),
                      flip_prob=0.01)
    res = decode(spec, rx, backend="seqparallel", mesh=mesh11)
    assert float((res.info_bits != bits).mean()) < 0.05
