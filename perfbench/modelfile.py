"""The benchmark's own model and tokenizer files, made from `--seed`.

The server loads a `.m` model file and a `.t` tokenizer file (the reference
project's formats). The benchmark writes both itself, so that the weights the
plain reference reads are the benchmark's and not something the program made:

* `.m`: magic, header size, (key, value) int32 pairs, then the tensors in the
  fixed walk order (embedding f32; per layer q, k, v, wo, w1, w2, w3 in Q40,
  q_norm, k_norm, norm0, norm1 in f32; final_norm f32; wcls Q40). A Q40 block
  is 18 bytes for 32 weights: an f16 scale `d`, then 16 bytes whose low nibble
  is weight j and high nibble weight j+16; the weight is `(nibble - 8) * d`.
  The codes are uniform random nibbles (-7..7, zero twice as likely) and the
  scales are drawn from a narrow table, so writing is a stream of random
  bytes (GB/s), not a quantizer.
* `.t`: a vocabulary in which every token id has a printable text of its own
  that tokenizes back to that id alone: `CODE_LEN` characters over an alphabet
  that no single-character token uses. A prompt's ids are therefore the
  benchmark's choice, and the ids a stream served are read back from its text.

Nothing here imports the program.
"""

from __future__ import annotations

import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

M_MAGIC = 0x0A00ABCD
T_MAGIC = 0x567124
ARCH_QWEN3 = 0xABCD01
F32, Q40 = 0, 2
Q_BLOCK, Q40_BYTES = 32, 18
# .m header keys
K_VERSION, K_ARCH, K_DIM, K_HIDDEN, K_LAYERS, K_HEADS, K_KV_HEADS = 0, 1, 2, 3, 4, 5, 6
K_EXPERTS, K_ACTIVE, K_VOCAB, K_SEQ, K_ACT, K_THETA, K_WTYPE = 7, 8, 9, 10, 11, 12, 13
K_ROPE_TYPE, K_HEAD_DIM, K_EPS = 18, 19, 20
ACT_SILU, ROPE_FALCON = 1, 1

WEIGHT_STD = 0.02  # what the codes times the mean scale come to
SCALE_TABLE = (  # 256 f16 scales, 0.75..1.25 of the mean: blocks differ
    np.linspace(0.75, 1.25, 256) * (WEIGHT_STD / np.sqrt(17.5))
).astype(np.float16)
PIECE_BLOCKS = 1 << 18  # Q40 blocks per work item (8M weights, 4.7 MB)

CHATML = (
    "{% for m in messages %}<|im_start|>{{ m['role'] }}\n{{ m['content'] }}"
    "<|im_end|>\n{% endfor %}{% if add_generation_prompt %}"
    "<|im_start|>assistant\n{% endif %}"
)
ALPHABET = "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
CODE_LEN = 4


def model_shape(cfg: dict) -> dict:
    """The sizes the file needs, from a configuration file's published keys."""
    return dict(
        dim=cfg["hidden_size"], ffn=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], seq_len=cfg["max_position_embeddings"],
        theta=int(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
    )


def header_pairs(s: dict) -> list:
    eps_code = {1e-5: 5, 1e-6: 6}[s["eps"]]
    return [
        (K_VERSION, 1), (K_ARCH, ARCH_QWEN3), (K_DIM, s["dim"]), (K_HIDDEN, s["ffn"]),
        (K_LAYERS, s["layers"]), (K_HEADS, s["heads"]), (K_KV_HEADS, s["kv_heads"]),
        (K_EXPERTS, 0), (K_ACTIVE, 0), (K_VOCAB, s["vocab"]), (K_SEQ, s["seq_len"]),
        (K_ACT, ACT_SILU), (K_THETA, s["theta"]), (K_WTYPE, Q40),
        (K_ROPE_TYPE, ROPE_FALCON), (K_HEAD_DIM, s["head_dim"]), (K_EPS, eps_code),
    ]


def tensor_walk(s: dict) -> list:
    """[(name, (out, in) or (n,), type, offset, n_bytes)] in file order."""
    q_dim, kv_dim = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    walk = [("embedding", (s["vocab"], s["dim"]), F32)]
    for l in range(s["layers"]):
        walk += [
            (f"q.{l}", (q_dim, s["dim"]), Q40), (f"k.{l}", (kv_dim, s["dim"]), Q40),
            (f"v.{l}", (kv_dim, s["dim"]), Q40), (f"wo.{l}", (s["dim"], q_dim), Q40),
            (f"w1.{l}", (s["ffn"], s["dim"]), Q40), (f"w2.{l}", (s["dim"], s["ffn"]), Q40),
            (f"w3.{l}", (s["ffn"], s["dim"]), Q40),
            (f"q_norm.{l}", (s["head_dim"],), F32), (f"k_norm.{l}", (s["head_dim"],), F32),
            (f"norm0.{l}", (s["dim"],), F32), (f"norm1.{l}", (s["dim"],), F32),
        ]
    walk += [("final_norm", (s["dim"],), F32), ("wcls", (s["vocab"], s["dim"]), Q40)]
    out, off = [], 8 + 8 * len(header_pairs(s))
    for name, shape, ft in walk:
        n = int(np.prod(shape))
        nb = n * 4 if ft == F32 else n // Q_BLOCK * Q40_BYTES
        out.append((name, shape, ft, off, nb))
        off += nb
    return out


def _piece(seed: int, ti: int, pi: int, name: str, ft: int, n: int) -> np.ndarray:
    """Bytes of `n` elements of tensor `ti`, piece `pi`: a function of the
    seed and the indices alone."""
    bits = np.random.default_rng([seed, ti, pi]).bit_generator
    if ft == F32:
        # uniform from raw bits (23 mantissa bits under exponent 0): [-0.5, 0.5)
        u = bits.random_raw((n + 1) // 2).view(np.uint32)[:n]
        u >>= 9
        u |= 0x3F800000
        x = u.view(np.float32)
        x -= np.float32(1.5)
        if "norm" in name:
            x *= np.float32(0.02)
            x += np.float32(1.0)
        else:
            x *= np.float32(WEIGHT_STD * np.sqrt(12.0))
        return x.view(np.uint8)
    nb = n // Q_BLOCK
    raw = bits.random_raw((nb * Q40_BYTES + 7) // 8).view(np.uint8)[: nb * Q40_BYTES]
    raw = raw.reshape(nb, Q40_BYTES)
    raw[:, :2] = SCALE_TABLE[raw[:, 0]].view(np.uint8).reshape(nb, 2)
    # code 0 (-8) has no opposite: send it to 8 (zero), so that the weights
    # are symmetric about zero (a common offset in every row would make one
    # token the best at every position, and the comparison vacuous)
    codes = raw[:, 2:]
    codes |= ((codes & 0x0F) == 0).astype(np.uint8) << 3
    codes |= ((codes & 0xF0) == 0).astype(np.uint8) << 7
    return raw.reshape(-1)


def write_model(path: str, cfg: dict, seed: int) -> int:
    """Write the `.m` file of `cfg` for `seed`; returns its size in bytes."""
    s = model_shape(cfg)
    pairs = header_pairs(s)
    jobs = []
    for ti, (name, shape, ft, _off, _nb) in enumerate(tensor_walk(s)):
        n = int(np.prod(shape))
        step = PIECE_BLOCKS * Q_BLOCK
        for pi, e0 in enumerate(range(0, n, step)):
            jobs.append((seed, ti, pi, name, ft, min(step, n - e0)))
    threads = min(16, os.cpu_count() or 1)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f, ThreadPoolExecutor(threads) as pool:
        f.write(struct.pack("<ii", M_MAGIC, 8 + 8 * len(pairs)))
        f.write(b"".join(struct.pack("<ii", k, v) for k, v in pairs))
        # bounded look-ahead: results are written in walk order
        window = 4 * threads
        futures = [pool.submit(_piece, *j) for j in jobs[:window]]
        for i in range(len(jobs)):
            f.write(memoryview(futures[i].result()))
            futures[i] = None
            if i + window < len(jobs):
                futures.append(pool.submit(_piece, *jobs[i + window]))
        size = f.tell()
    os.replace(tmp, path)
    return size


def ensure_model(work: str, name: str, cfg: dict, seed: int) -> tuple:
    """The model file of (configuration, seed) under `work`, written if it is
    not there. One file per configuration is kept: a run with another seed
    replaces it, so the checkout never holds more than one model per
    configuration. Returns (path, reused)."""
    os.makedirs(work, exist_ok=True)
    path = os.path.join(work, f"{name}.seed{seed}.m")
    s = model_shape(cfg)
    last = tensor_walk(s)[-1]
    want = last[3] + last[4]
    if os.path.exists(path) and os.path.getsize(path) == want:
        return path, True
    for old in os.listdir(work):
        if old.startswith(f"{name}.seed") and (old.endswith(".m") or old.endswith(".tmp")):
            os.remove(os.path.join(work, old))
    write_model(path, cfg, seed)
    return path, False


# -- the reference's side: read the benchmark's own file back -------------------


class ModelFile:
    """Raw tensors of a file `write_model` wrote (np.memmap views)."""

    def __init__(self, path: str, cfg: dict):
        self.shape = model_shape(cfg)
        self.mm = np.memmap(path, dtype=np.uint8, mode="r")
        self.index = {n: (shape, ft, off, nb) for n, shape, ft, off, nb in tensor_walk(self.shape)}

    def raw(self, name: str) -> np.ndarray:
        _shape, _ft, off, nb = self.index[name]
        return self.mm[off : off + nb]

    def f32(self, name: str) -> np.ndarray:
        shape, ft, off, nb = self.index[name]
        assert ft == F32
        return np.frombuffer(self.mm, dtype=np.float32, count=nb // 4, offset=off).reshape(shape)

    def rows_f32(self, name: str, rows) -> np.ndarray:
        """Selected rows of an f32 matrix (the embedding's)."""
        shape, ft, off, _nb = self.index[name]
        assert ft == F32
        out = np.empty((len(rows), shape[1]), np.float32)
        for i, r in enumerate(rows):
            out[i] = np.frombuffer(self.mm, np.float32, shape[1], off + int(r) * shape[1] * 4)
        return out

    def close(self):
        del self.mm


def dequant_q40_host(raw: np.ndarray, shape: tuple) -> np.ndarray:
    """Plain numpy Q40 decode of a whole tensor (tests and tiny sizes)."""
    blocks = np.asarray(raw).reshape(-1, Q40_BYTES)
    d = blocks[:, :2].copy().view(np.float16).astype(np.float32)
    lo = (blocks[:, 2:] & 0x0F).astype(np.float32) - 8.0
    hi = (blocks[:, 2:] >> 4).astype(np.float32) - 8.0
    return (np.concatenate([lo, hi], axis=1) * d).reshape(shape)


# -- tokenizer ------------------------------------------------------------------


def vocab_pieces(vocab: int) -> list:
    """Token id -> its bytes, for a model of `vocab` ids. Ids 0..n_single-1
    are the ASCII characters that are not in ALPHABET (the chat template needs
    some of them); then CODE_LEN-character codes up to the model's last id.
    Three more ids follow, past the model's logits: bos (never added, as in
    Qwen3's own tokenizer), eos and eot. The model cannot emit them, so no
    request of a random model stops before its max_tokens."""
    single = [bytes([c]) for c in range(128) if chr(c) not in ALPHABET]
    n_codes = vocab - len(single)
    assert 0 < n_codes <= len(ALPHABET) ** CODE_LEN
    pieces = list(single)
    for i in range(n_codes):
        code, x = [], i
        for _ in range(CODE_LEN):
            code.append(ALPHABET[x % len(ALPHABET)])
            x //= len(ALPHABET)
        pieces.append("".join(code).encode())
    return pieces + [b"<s>", b"</s>", b"<|eot|>"]


class Vocabulary:
    """Text <-> ids for the benchmark's tokenizer, without the program."""

    def __init__(self, vocab: int):
        self.pieces = vocab_pieces(vocab)
        self.n_single = sum(1 for p in self.pieces[:128] if len(p) == 1)
        self.first_code = self.n_single
        self.n_codes = vocab - self.n_single
        self._single = {p[0]: i for i, p in enumerate(self.pieces[: self.n_single])}
        self._digit = {c: i for i, c in enumerate(ALPHABET)}

    def text(self, ids) -> str:
        return b"".join(self.pieces[i] for i in ids).decode("ascii")

    def ids(self, text: str) -> list:
        """The ids whose pieces spell `text` (what the server's tokenizer
        gives for it, and what a stream's text decodes back to)."""
        out, i, n = [], 0, len(text)
        while i < n:
            c = text[i]
            if c in self._digit:
                code = text[i : i + CODE_LEN]
                if len(code) < CODE_LEN:
                    raise ValueError(f"truncated token code {code!r}")
                x = 0
                for ch in reversed(code):
                    x = x * len(ALPHABET) + self._digit[ch]
                out.append(self.first_code + x)
                i += CODE_LEN
            else:
                out.append(self._single[ord(c)])
                i += 1
        return out

    def chat_ids(self, messages: list) -> list:
        """Token ids of a chat request as the server's ChatML template and
        tokenizer make them (no bos is added)."""
        text = "".join(
            f"<|im_start|>{m['role']}\n{m['content']}<|im_end|>\n" for m in messages
        ) + "<|im_start|>assistant\n"
        return self.ids(text)


def write_tokenizer(path: str, vocab: int) -> None:
    pieces = vocab_pieces(vocab)
    bos = vocab  # the first id past the model's
    template = CHATML.encode()
    # version, vocabulary size, longest piece, bos id, add_bos = 0, template, 2 eos ids
    kv = [(0, 1), (1, len(pieces)), (2, max(len(p) for p in pieces)), (3, bos), (10, 0),
          (7, len(template)), (9, 2)]
    body = b"".join(struct.pack("<ii", k, v) for k, v in kv)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(struct.pack("<ii", T_MAGIC, 8 + len(body)))
        f.write(body)
        f.write(template)
        f.write(struct.pack("<ii", bos + 1, bos + 2))
        f.write(b"".join(struct.pack("<fi", 0.0, len(p)) + p for p in pieces))
    os.replace(tmp, path)
