"""Faults planted in the program, and the control, for the checks' own tests.

Each is a context manager that patches the program where a later change
could go wrong, so that a run under it must come out not correct.  The
benchmark's own runs never enter them; `control.py` and `tests/` do.

  skip_verify      the control: alignment accepts every voted placement
                   without the Hamming verification, reporting the overlap
                   as its matches (breaks the stated identity guarantee);
  state_unchanged  the k=21 round gets the k=17 round's k-mer set back;
  half_batch       k-mer analysis counts the first half of the reads only;
  altered_base     one base of every rendered scaffold changes where the
                   scaffolds are produced;
  shifted_align    every final placement moves one base along its contig;
  drop_contig      pruning also removes the shortest live contig of at
                   least 2,000 bases, so part of a genome goes missing
                   while every live contig stays sound;
  misjoin          scaffolding joins its two longest scaffolds end to end
                   with no link between them.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value, *, clear=False):
    import jax

    old = getattr(obj, name)
    setattr(obj, name, value)
    if clear:
        jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, old)
        if clear:
            jax.clear_caches()


def skip_verify():
    from repro.core import alignment

    verify = alignment._verify

    def unverified(reads, contigs, cid, cstart, orient):
        _, overlap = verify(reads, contigs, cid, cstart, orient)
        return overlap, overlap

    return _patch(alignment, "_verify", unverified, clear=True)


def state_unchanged():
    from repro.api.context import Local

    kmer_set = Local.kmer_set

    def first_round_only(self, k, prev):
        if prev is None or not hasattr(self, "_first_kset"):
            self._first_kset = kmer_set(self, k, prev)
        return self._first_kset

    return _patch(Local, "kmer_set", first_round_only)


def half_batch():
    import jax.numpy as jnp

    from repro.core import kmer_analysis

    count = kmer_analysis.count_occurrences

    def first_half(hi, lo, left, right, valid, **kw):
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return count(hi, lo, left, right, valid & keep, **kw)

    return _patch(kmer_analysis, "count_occurrences", first_half)


def altered_base():
    from repro.core import gap_closing

    close = gap_closing.close_and_render

    def altered(*args, **kw):
        seqs = close(*args, **kw)
        b = seqs.bases
        hit = seqs.lengths > 100
        b = b.at[:, 100].set((b[:, 100] + hit.astype(b.dtype)) % 4)
        return seqs._replace(bases=b)

    return _patch(gap_closing, "close_and_render", altered)


def shifted_align():
    from repro.api.context import Local

    align = Local.align

    def shifted(self, contigs, alive, k):
        al = align(self, contigs, alive, k)
        return al._replace(cstart=al.cstart + 1)

    return _patch(Local, "align", shifted)


def drop_contig():
    import jax.numpy as jnp

    from repro.core import pruning

    prune = pruning.prune

    def dropping(lengths, depths, ends_nbr, alive_in, **kw):
        res = prune(lengths, depths, ends_nbr, alive_in, **kw)
        long = res.alive & (lengths >= 2000)
        target = jnp.argmin(jnp.where(long, lengths, jnp.iinfo(
            lengths.dtype).max))
        keep = (jnp.arange(lengths.shape[0]) != target) | ~long.any()
        return res._replace(alive=res.alive & keep)

    return _patch(pruning, "prune", dropping)


def misjoin():
    import jax.numpy as jnp
    import numpy as np

    from repro.core import scaffolding

    build = scaffolding.scaffold_from_links

    def joined(links, contigs, alive, insert_size, **kw):
        scaffs, *rest = build(links, contigs, alive, insert_size, **kw)
        sc = {k: np.array(v) for k, v in scaffs._asdict().items()}
        lens = np.asarray(contigs.lengths)
        n, M = int(sc["n_scaffolds"]), sc["contig"].shape[1]
        total = [lens[sc["contig"][s, :sc["n_members"][s]]].sum()
                 for s in range(n)]
        order = np.argsort(total)[::-1]
        a, b = (int(x) for x in order[:2]) if n >= 2 else (0, 0)
        na, nb = int(sc["n_members"][a]), int(sc["n_members"][b])
        if a != b and na + nb <= M:
            for key in ("contig", "orient", "gap"):
                sc[key][a, na:na + nb] = sc[key][b, :nb]
            sc["gap"][a, na - 1] = 10.0
            sc["contig"][b] = -1
            sc["n_members"][a], sc["n_members"][b] = na + nb, 0
        scaffs = type(scaffs)(**{k: jnp.asarray(v) for k, v in sc.items()})
        return (scaffs, *rest)

    return _patch(scaffolding, "scaffold_from_links", joined)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_base": altered_base, "shifted_align": shifted_align,
          "drop_contig": drop_contig, "misjoin": misjoin}
