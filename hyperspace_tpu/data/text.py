"""Text-classification data (reference workload 3: HyboNet text-clf).

No network access in this environment, so the loader reads a simple
``label<TAB>text`` TSV when present (whitespace tokenization, vocab built
from the training split) and otherwise synthesizes a classification corpus
with class-dependent token distributions — enough signal to verify the
HyboNet encoder learns (SURVEY.md §4.7 integration-test strategy).

Sequences are padded to ``max_len`` with id 0 (PAD) and carried with a mask
— static shapes for XLA, like every other loader here.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from hyperspace_tpu.telemetry.trace import span

PAD_ID = 0


@dataclasses.dataclass
class TextDataset:
    tokens: np.ndarray  # [N, L] int32, 0 = pad
    mask: np.ndarray  # [N, L] bool
    labels: np.ndarray  # [N] int32
    vocab_size: int
    num_classes: int

    def split(self, train_frac: float = 0.8, seed: int = 0):
        rng = np.random.default_rng(seed)
        perm = rng.permutation(len(self.labels))
        n_tr = int(len(perm) * train_frac)
        tr, te = perm[:n_tr], perm[n_tr:]
        pick = lambda idx: TextDataset(
            self.tokens[idx], self.mask[idx], self.labels[idx],
            self.vocab_size, self.num_classes)
        return pick(tr), pick(te)


def _pad(seqs: list[list[int]], max_len: int):
    n = len(seqs)
    toks = np.zeros((n, max_len), np.int32)
    mask = np.zeros((n, max_len), bool)
    for i, s in enumerate(seqs):
        s = s[:max_len]
        toks[i, : len(s)] = s
        mask[i, : len(s)] = True
    return toks, mask


def load_tsv(path: str, max_len: int = 64, max_vocab: int = 30000) -> TextDataset:
    """``label<TAB>text`` lines; builds a frequency-capped vocab (1 = UNK)."""
    rows = []
    with open(path) as f:
        for line in f:
            parts = line.rstrip("\n").split("\t", 1)
            if len(parts) == 2:
                rows.append((parts[0], parts[1].lower().split()))
    labels_map: dict[str, int] = {}
    freq: dict[str, int] = {}
    for lab, toks in rows:
        labels_map.setdefault(lab, len(labels_map))
        for t in toks:
            freq[t] = freq.get(t, 0) + 1
    vocab = {t: i + 2 for i, (t, _) in enumerate(
        sorted(freq.items(), key=lambda kv: -kv[1])[: max_vocab - 2])}
    seqs = [[vocab.get(t, 1) for t in toks] for _, toks in rows]
    toks, mask = _pad(seqs, max_len)
    labels = np.asarray([labels_map[lab] for lab, _ in rows], np.int32)
    return TextDataset(toks, mask, labels, len(vocab) + 2, len(labels_map))


def synthetic_text(
    num_samples: int = 2048,
    vocab_size: int = 512,
    num_classes: int = 4,
    max_len: int = 32,
    min_len: int = 8,
    class_sharpness: float = 3.0,
    seed: int = 0,
) -> TextDataset:
    """Class-dependent unigram corpora (ids 0/1 reserved for PAD/UNK)."""
    rng = np.random.default_rng(seed)
    usable = vocab_size - 2
    logits = class_sharpness * rng.normal(size=(num_classes, usable))
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    labels = rng.integers(0, num_classes, num_samples).astype(np.int32)
    seqs = []
    for y in labels:
        ln = int(rng.integers(min_len, max_len + 1))
        seqs.append(list(rng.choice(usable, size=ln, p=probs[y]) + 2))
    toks, mask = _pad(seqs, max_len)
    return TextDataset(toks, mask, labels, vocab_size, num_classes)


def load_text(name: str, root: str | None = None, **synth_kw) -> tuple[TextDataset, str]:
    if root is not None:
        path = os.path.join(root, f"{name}.tsv")
        if os.path.exists(path):
            return load_tsv(path), "disk"
    return synthetic_text(**synth_kw), "synthetic"


# --- a packed token stream for language-model pretraining (models/looplm.py) --

EOD_ID = 0


def synthetic_token_stream(
    num_tokens: int,
    vocab_size: int,
    zipf_exponent: float = 1.0,
    doc_len_median: float = 512.0,
    doc_len_sigma: float = 1.2,
    doc_len_min: int = 16,
    doc_len_max: int = 4096,
    seed: int = 0,
) -> np.ndarray:
    """``num_tokens`` int32 ids, packed without padding: documents of
    log-normal length (clipped to [min, max]) whose ids are Zipf-
    distributed over 1 … vocab_size-1 (rank r with weight r^-exponent),
    each closed by ``EOD_ID``.  No network here, so the stream is
    generated; its shape (a long-tailed unigram law, documents far
    shorter and a few longer than the context) is what a packer feeds a
    pretraining job."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(zipf_exponent))
    ids = 1 + np.searchsorted(cdf, rng.random(num_tokens) * cdf[-1])
    out = np.minimum(ids, vocab_size - 1).astype(np.int32)
    # document ends: draw lengths until they cover the stream
    mean_len = doc_len_median * np.exp(0.5 * doc_len_sigma ** 2)
    draw = int(2 * num_tokens / max(min(mean_len, doc_len_max), 1)) + 16
    ends = np.empty(0, np.int64)
    while ends.size == 0 or ends[-1] < num_tokens:
        lens = np.clip(np.rint(rng.lognormal(
            np.log(doc_len_median), doc_len_sigma, draw)),
            doc_len_min, doc_len_max).astype(np.int64)
        start = 0 if ends.size == 0 else ends[-1] + 1
        ends = np.concatenate([ends, start + np.cumsum(lens + 1) - 1])
    out[ends[ends < num_tokens]] = EOD_ID
    return out


def ensure_token_stream(root: str, seed: int = 0, **stream_kw) -> str:
    """``<root>/tokens.npy`` holding :func:`synthetic_token_stream` of
    ``seed`` and ``stream_kw``, written once (atomically: a reader never
    sees half a file); returns its path."""
    path = os.path.join(root, "tokens.npy")
    if not os.path.exists(path):
        with span("make_dataset", {"dataset": "token_stream"}):
            os.makedirs(root, exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp.npy"
            np.save(tmp, synthetic_token_stream(seed=seed, **stream_kw))
            os.replace(tmp, path)
    return path


def load_token_stream(root: str | None = None, **synth_kw) -> tuple[np.ndarray, str]:
    """(tokens, source): ``<root>/tokens.npy`` when it is there ("disk"),
    else a stream synthesized from ``synth_kw`` ("synthetic")."""
    info = {}
    with span("load_stream", info):
        path = None if root is None else os.path.join(root, "tokens.npy")
        if path is not None and os.path.exists(path):
            tokens, source = np.load(path), "disk"
        else:
            tokens, source = synthetic_token_stream(**synth_kw), "synthetic"
        info.update(tokens=int(tokens.size), source=source)
    return tokens, source
