"""Shared test utilities: genome/contig comparison oracles, and the runner
of scripts that need 8 host devices."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Time limit of one `run_devices_script` call: about three times its
# slowest caller (`test_mesh_walk_backend_parity`, 115 s on 8 CPU cores
# beside five other test workers), so a Mesh stage that starts compiling
# per call again fails with its output instead of eating the whole
# suite's clock.
DEVICES_SCRIPT_TIMEOUT_S = 360

_RC = np.array([3, 2, 1, 0, 4], dtype=np.uint8)


def rc_np(seq):
    return _RC[np.asarray(seq)[::-1]]


def seq_str(seq):
    return "".join("ACGTN"[int(b)] for b in np.asarray(seq))


def contig_list(contigs, min_len=0):
    """Extract live contigs from a ContigSet as a list of np arrays."""
    bases = np.asarray(contigs.bases)
    lengths = np.asarray(contigs.lengths)
    out = []
    for i in range(len(lengths)):
        if lengths[i] >= max(min_len, 1):
            out.append(bases[i, : lengths[i]])
    return out


def is_substring(needle: np.ndarray, hay: np.ndarray) -> bool:
    s, h = seq_str(needle), seq_str(hay)
    return s in h


def matches_genome(contig, genome) -> bool:
    """contig is an exact substring of genome or its reverse complement."""
    return is_substring(contig, genome) or is_substring(contig, rc_np(genome))


def genome_coverage(contigs_list, genome, w=30) -> float:
    """metaQUAST-style genome fraction: a genome position is covered when
    the w-mer window starting there occurs in some contig (either strand).
    One wrong base in a contig only uncovers a w-wide window, mirroring
    aligned-block coverage rather than exact containment."""
    windows = set()
    for c in contigs_list:
        s = seq_str(c)
        sr = seq_str(rc_np(c))
        for src in (s, sr):
            for i in range(len(src) - w + 1):
                windows.add(src[i : i + w])
    g = seq_str(genome)
    n = len(g) - w + 1
    if n <= 0:
        return 0.0
    hit = sum(1 for i in range(n) if g[i : i + w] in windows)
    return hit / n


def run_devices_script(body: str) -> str:
    """Run `body` in a fresh interpreter that sees 8 CPU host devices.

    XLA_FLAGS must be set before jax is imported, and the test workers'
    own jax sees one device, so each multi-device test starts its own
    interpreter.  Fails, showing whatever the script printed, if it exits
    non-zero or runs past DEVICES_SCRIPT_TIMEOUT_S.
    """
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        import jax.numpy as jnp
        assert jax.device_count() == 8, jax.device_count()
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src:{REPO}/tests:{REPO}"
    try:
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env=env, timeout=DEVICES_SCRIPT_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired as e:
        pytest.fail(
            f"script ran past {DEVICES_SCRIPT_TIMEOUT_S} s\n"
            f"STDOUT:\n{_decoded(e.stdout)}\nSTDERR:\n{_decoded(e.stderr)}"
        )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def _decoded(captured) -> str:
    # what a timed-out run captured comes back as bytes, whatever `text` says
    if isinstance(captured, bytes):
        return captured.decode(errors="replace")
    return captured or ""
