"""Seeded pass files in the program's slot text format.

One general generator: every parameter (field cardinalities, key law,
slot lengths, label model) comes from the configuration file, every size
(examples, files) from the traffic file, every random draw from
``(seed, pass_id, file_id)``.  The format is the one
``chip_smoke.write_pass_file`` writes (``1 <label> <D> <dense...>`` then
``<n> <key...>`` per sparse slot); the traffic is not: keys are Zipf
within a Criteo field, slots may be multi-valued, and labels depend on
hidden per-key weights so that embeddings have something to learn.

Numbers are written fixed-width (keys and counts zero-padded), which lets
NumPy assemble a whole file as one byte array; both of the program's
parsers read a zero-padded decimal as the number it is.
"""

from __future__ import annotations

import concurrent.futures
import os
from typing import Dict, List, Sequence

import numpy as np

# a prime above every field cardinality: rank -> (rank * PRIME) % V is a
# permutation of 0..V-1, so a field's hot keys are spread over its key
# range instead of sitting at its low end (feasigns are hashes in a
# deployment; rows sorted by key would otherwise cluster the hot ones)
PRIME = 2654435761
DENSE_SCALE = 10_000          # dense values are k / 1e4 in [0, 1), "0.dddd"
HEAD_LABEL = 2                # byte of the label digit in a line's head
N_HOT = 8                     # probe keys taken from a pass's hottest
CHUNK = 32_768                # lines drawn and written at a time


class Fields:
    """The key space of a configuration: one contiguous key range a
    field, key 0 reserved (the program's zero row)."""

    def __init__(self, cfg: dict):
        self.vocab = np.asarray(cfg["fields"]["vocab"], np.int64)
        self.n_slots = len(self.vocab)
        self.dense_dim = int(cfg["fields"]["dense_dim"])
        self.offsets = np.concatenate([[0], np.cumsum(self.vocab)[:-1]])
        self.exponent = float(cfg["keys"]["exponent"])
        self.key_digits = len(str(int(self.vocab.sum())))
        lengths = cfg.get("lengths") or {}
        self.multi = np.zeros(self.n_slots, bool)
        self.multi[list(lengths.get("slots", []))] = True
        self.len_law = lengths
        self.label = cfg["label_model"]

    def keys_of(self, slot: np.ndarray, rank: np.ndarray) -> np.ndarray:
        """Key of 0-based popularity ``rank`` within ``slot``."""
        v = self.vocab[slot]
        return 1 + self.offsets[slot] + (rank * PRIME) % v


def zipf_ranks(u: np.ndarray, vocab: np.ndarray, exponent: float
               ) -> np.ndarray:
    """0-based ranks from uniforms: a bounded power law made discrete,
    P(rank = k) ~ (k+1)^(1-a) - (k+2)^(1-a), which is Zipf's k^-a to
    first order.  One log and one exp a draw, so a pass is made in
    bulk; with a scalar ``vocab`` nothing is looked up per draw."""
    a = 1.0 - exponent
    x = np.exp(np.log1p(u * (np.power(vocab + 1.0, a) - 1.0)) / a)
    return np.minimum(x, vocab).astype(np.int64) - 1


def hidden_weights(fields: Fields, seed: int) -> Dict[int, np.ndarray]:
    """The label model's per-key weights, one vector a label slot, fixed
    for a run (every pass of a seed shares them)."""
    return {int(s): np.random.default_rng([seed, 7919, int(s)]).normal(
                0.0, fields.label["key_sigma"], int(fields.vocab[s]))
            for s in fields.label["slots"]}


def draw_file(fields: Fields, n: int, seed: int, pass_id: int,
              file_id: int, chunk: int = 0) -> dict:
    """``n`` examples of one file (its ``chunk``-th run of lines) as
    arrays: ``lens`` [n, S], ``keys`` flat in file order (example, slot,
    position), ``dense`` [n, D] ints in 1e-4 units, ``labels`` [n]."""
    rng = np.random.default_rng([seed, pass_id, file_id, chunk])
    s = fields.n_slots
    lens = np.ones((n, s), np.int64)
    if fields.multi.any():
        law = fields.len_law
        raw = np.exp(rng.normal(np.log(law["median"]), law["sigma"],
                                (n, int(fields.multi.sum()))))
        lens[:, fields.multi] = np.clip(np.rint(raw), max(1, law["min"]),
                                        law["max"]).astype(np.int64)
    flat_lens = lens.ravel()
    starts = (np.cumsum(flat_lens) - flat_lens).reshape(n, s)
    # one slot at a time, so that the field's cardinality and offset are
    # scalars and the remainder is by a constant
    u = rng.random(int(flat_lens.sum()))
    keys = np.empty(u.size, np.int64)
    done = 0
    for slot in range(s):
        m = int(lens[:, slot].sum())
        k = fields.keys_of(slot, zipf_ranks(
            u[done:done + m], fields.vocab[slot], fields.exponent))
        done += m
        if m == n:
            keys[starts[:, slot]] = k
        else:
            ln = lens[:, slot]
            within = np.arange(m) - np.repeat(np.cumsum(ln) - ln, ln)
            keys[np.repeat(starts[:, slot], ln) + within] = k
    dense = rng.integers(0, DENSE_SCALE, (n, fields.dense_dim))
    # labels: a dense feature plus hidden weights of small-vocabulary
    # slots (their first key), so both the tower and embed_w must learn
    lab = fields.label
    logit = lab["bias"] + lab["dense_weight"] * (
        dense[:, 0] / DENSE_SCALE - 0.5)
    for slot, w in hidden_weights(fields, seed).items():
        logit = logit + w[keys[starts[:, slot]] - 1 - fields.offsets[slot]]
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int64)
    return {"lens": lens, "keys": keys, "dense": dense, "labels": labels}


def _four_digit_table() -> np.ndarray:
    t = np.arange(10_000)
    ascii4 = np.stack([t // 1000, t // 100 % 10, t // 10 % 10, t % 10],
                      axis=1).astype(np.uint8) + 48
    return ascii4.view(np.uint32).ravel()


_FOUR = _four_digit_table()    # 0..9999 -> its four ASCII digits, one word


def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """[..., width] ASCII digits of ints below 2**32, zero-padded: four
    digits at a time through a table, one division a group."""
    v = values.astype(np.uint32)
    groups = -(-width // 4)
    out = np.empty(values.shape + (groups,), np.uint32)
    for g in range(groups - 1, -1, -1):
        q = v // np.uint32(10_000)
        out[..., g] = _FOUR[v - q * np.uint32(10_000)]
        v = q
    return out.view(np.uint8).reshape(
        values.shape + (4 * groups,))[..., 4 * groups - width:]


HEAD_PAD = 4       # a line's head is padded to whole 4-byte words
TOKEN = 12         # "cc kkkkkkkk " / "   kkkkkkkk ": three words a key


def _head_bytes(fields: Fields, labels: np.ndarray, dense: np.ndarray
                ) -> np.ndarray:
    """[n, H] bytes of ``1 <label> <D> d.dddd ... `` (trailing spaces up
    to a multiple of four)."""
    n, d = dense.shape
    dd = str(d).encode()
    width = 4 + len(dd) + 1 + 7 * d
    head = np.full((n, -(-width // HEAD_PAD) * HEAD_PAD), 32, np.uint8)
    head[:, 0] = 49                                   # "1": one label
    head[:, HEAD_LABEL] = 48 + labels
    head[:, 4:4 + len(dd)] = np.frombuffer(dd, np.uint8)
    dig = _digits(dense, 5)                           # [n, d, 5]
    cell = np.full((n, d, 7), 32, np.uint8)
    cell[:, :, 0] = dig[:, :, 0]
    cell[:, :, 1] = 46                                # "."
    cell[:, :, 2:6] = dig[:, :, 1:]
    head[:, 5 + len(dd):width] = cell.reshape(n, 7 * d)
    return head


def file_bytes(fields: Fields, ex: dict) -> np.ndarray:
    """One file's text as a flat uint8 array.  Every key is one 12-byte
    token, the first of a slot led by the slot's count, and the head is
    whole words too, so a ragged file is assembled by scattering 4-byte
    words and a file of single-valued slots by one concatenation."""
    lens, keys = ex["lens"], ex["keys"]
    n, s = lens.shape
    w = fields.key_digits
    if w + 4 > TOKEN:
        raise ValueError(f"keys of {w} digits do not fit a {TOKEN}-byte "
                         "token")
    flat_lens = lens.ravel()
    first = np.cumsum(flat_lens) - flat_lens          # slot's first token
    tok = np.full((keys.size, TOKEN), 32, np.uint8)
    tok[:, TOKEN - 1 - w:TOKEN - 1] = _digits(keys, w)
    tok[first, 0:2] = _digits(flat_lens, 2)
    head = _head_bytes(fields, ex["labels"], ex["dense"])
    per_line = lens.sum(axis=1)
    last = np.cumsum(per_line) - 1                    # line's last token
    tok[last, TOKEN - 1] = 10
    if per_line.max() == per_line.min():
        return np.concatenate(
            [head, tok.reshape(n, -1)], axis=1).reshape(-1)
    hw, tw = head.shape[1] // 4, TOKEN // 4
    line_start = np.cumsum(hw + tw * per_line) - (hw + tw * per_line)
    out = np.empty(int(n * hw + tw * keys.size), np.uint32)
    out[line_start[:, None] + np.arange(hw)] = head.view(np.uint32)
    tok_start = (np.repeat(line_start + hw, per_line)
                 + tw * (np.arange(keys.size)
                         - np.repeat(last + 1 - per_line, per_line)))
    out[tok_start[:, None] + np.arange(tw)] = tok.view(np.uint32)
    return out.view(np.uint8)


def pick_probe_keys(keys: np.ndarray, seed: int, n_cold: int = 64
                    ) -> np.ndarray:
    """A fixed number of keys out of a run of examples, so that reading
    them back has one shape: its hottest and a seeded sample of the
    rest."""
    uniq, counts = np.unique(keys, return_counts=True)
    by_heat = uniq[np.argsort(counts, kind="stable")]
    hot, rest = by_heat[-N_HOT:], by_heat[:-N_HOT]
    cold = np.random.default_rng([seed, 104729]).choice(
        rest, size=min(n_cold, rest.size), replace=False)
    return np.sort(np.concatenate([hot, cold]))


def write_pass(directory: str, fields: Fields, seed: int, pass_id: int,
               n_examples: int, n_files: int, probe_keys=None) -> dict:
    """Write one pass as ``n_files`` files.  Returns what the run needs
    of it: file paths, key statistics (counted, not timed) and how often
    each probe key occurs in the pass, which is the write-back check's own
    count.  Without ``probe_keys`` the pass picks them from its first
    lines."""
    os.makedirs(directory, exist_ok=True)
    per = -(-n_examples // n_files)
    sizes = [max(0, min(per, n_examples - i * per)) for i in range(n_files)]
    if probe_keys is None:
        probe_keys = pick_probe_keys(
            draw_file(fields, min(sizes[0], CHUNK), seed, pass_id, 0)["keys"],
            seed)
    probe_keys = np.asarray(probe_keys, np.int64)      # sorted
    # every thread marks the keys it writes in one shared map: the writes
    # race, and all of them write the same 1
    seen = np.zeros(int(fields.vocab.sum()) + 1, np.uint8)

    def one(i: int):
        path = os.path.join(directory, f"part-{i:03d}.txt")
        counts = np.zeros(probe_keys.size, np.int64)
        occurrences = longest = 0
        with open(path, "wb") as fh:
            # in runs of CHUNK lines: arrays this small come from memory
            # the allocator already holds, where a file-sized array is
            # mapped afresh and pays a page fault for every page
            for c, lo in enumerate(range(0, sizes[i], CHUNK)):
                ex = draw_file(fields, min(CHUNK, sizes[i] - lo), seed,
                               pass_id, i, c)
                file_bytes(fields, ex).tofile(fh)
                keys = ex["keys"]
                seen[keys] = 1
                at = np.minimum(np.searchsorted(probe_keys, keys),
                                probe_keys.size - 1)
                counts += np.bincount(at[probe_keys[at] == keys],
                                      minlength=probe_keys.size)
                occurrences += int(keys.size)
                longest = max(longest, int(ex["lens"].max()))
        return path, counts, occurrences, longest

    # files are independent draws and NumPy releases the interpreter lock
    # in its loops, so threads cut the wall time of a deep pass
    todo = [i for i in range(n_files) if sizes[i] > 0]
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(os.cpu_count() or 1, len(todo))) as pool:
        done = list(pool.map(one, todo))
    occurrences = sum(d[2] for d in done)
    return {"files": [d[0] for d in done], "pass_id": pass_id, "seed": seed,
            "stats": {"examples": n_examples, "occurrences": occurrences,
                      "occurrences_per_example": occurrences / n_examples,
                      "max_slot_len": max(d[3] for d in done),
                      "unique_keys": int(np.count_nonzero(seen))},
            "probe": {"keys": [int(k) for k in probe_keys],
                      "counts": [int(c) for c in
                                 np.sum([d[1] for d in done], axis=0)]}}


def write_passes(root: str, fields: Fields, seed: int, n_passes: int,
                 n_examples: int, n_files: int) -> List[dict]:
    """``n_passes`` distinct passes under ``root``; the first picks the
    probe keys and every later pass counts the same ones."""
    metas: List[dict] = []
    for p in range(n_passes):
        probe = metas[0]["probe"]["keys"] if metas else None
        metas.append(write_pass(os.path.join(root, f"pass-{p:02d}"), fields,
                                seed, p, n_examples, n_files, probe))
    return metas


def probe_counts(metas: Sequence[dict], trained: Sequence[int]
                 ) -> Dict[int, int]:
    """Occurrences of every probe key over the passes trained, given as
    indices into ``metas`` (an index may repeat)."""
    total: Dict[int, int] = {}
    for p in trained:
        probe = metas[p]["probe"]
        for k, c in zip(probe["keys"], probe["counts"]):
            total[k] = total.get(k, 0) + c
    return total
