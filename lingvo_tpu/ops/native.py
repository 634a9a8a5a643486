"""ctypes bindings for the native input-pipeline library.

Python surface over lingvo_tpu/ops/cc: RecordYielder (sharded files, shuffle
ring, epochs — ref `record_yielder.cc`), weighted mixing, PackSequences (ref
`pack_ops.cc`), AsciiTokenizer / Vocab tokenizer (ref `tokenizer_ops`).
Builds the .so on first use (g++, ~2s) and caches it next to the sources.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_CC_DIR = os.path.join(os.path.dirname(__file__), "cc")
_SO_PATH = os.path.join(_CC_DIR, "liblingvo_tpu_ops.so")
_LIB = None
_LOCK = threading.Lock()


def _BuildIfNeeded():
  # Rebuild when the source *content* changes — mtimes are arbitrary after a
  # fresh checkout, so a stale .so could otherwise shadow newer sources.
  srcs = sorted(
      f for f in os.listdir(_CC_DIR)
      if f.endswith((".cc", ".h")) or f == "Makefile")
  digest = hashlib.sha256()
  for f in srcs:
    with open(os.path.join(_CC_DIR, f), "rb") as fh:
      digest.update(f.encode())
      digest.update(fh.read())
  stamp = os.path.join(_CC_DIR, ".build_hash")
  want = digest.hexdigest()
  have = None
  if os.path.exists(stamp):
    with open(stamp) as fh:
      have = fh.read().strip()
  if not os.path.exists(_SO_PATH) or have != want:
    try:
      subprocess.run(["make", "-C", _CC_DIR, "-s", "-B"], check=True)
    except (OSError, subprocess.CalledProcessError) as e:
      raise RuntimeError(
          "lingvo_tpu.ops.native: could not build the input-pipeline library "
          f"in {_CC_DIR} ({e}). It is built on first use from the committed "
          "sources and needs `make` and a C++17 compiler ($CXX, default "
          "g++); only file-based input generators and the C++ tokenizers "
          "use it.") from e
    with open(stamp, "w") as fh:
      fh.write(want)


def Lib() -> ctypes.CDLL:
  global _LIB
  with _LOCK:
    if _LIB is None:
      _BuildIfNeeded()
      lib = ctypes.CDLL(_SO_PATH)
      # signatures
      lib.LTYielderNew.restype = ctypes.c_void_p
      lib.LTYielderNew.argtypes = [
          ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int64, ctypes.c_int32,
          ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32
      ]
      lib.LTMixYielderNew.restype = ctypes.c_void_p
      lib.LTMixYielderNew.argtypes = [
          ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_double),
          ctypes.c_int32, ctypes.c_uint64
      ]
      lib.LTYielderNext.restype = ctypes.c_int64
      lib.LTYielderNext.argtypes = [
          ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
          ctypes.POINTER(ctypes.c_int32)
      ]
      lib.LTYielderEpochs.restype = ctypes.c_int64
      lib.LTYielderEpochs.argtypes = [ctypes.c_void_p]
      lib.LTYielderFree.argtypes = [ctypes.c_void_p]
      lib.LTPackSequences.restype = ctypes.c_int64
      lib.LTPackSequences.argtypes = [
          ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
          ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
          ctypes.POINTER(ctypes.c_int32), ctypes.c_int32
      ]
      lib.LTAsciiToIds.restype = ctypes.c_int32
      lib.LTAsciiToIds.argtypes = [
          ctypes.c_char_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
          ctypes.c_int32, ctypes.c_int32
      ]
      lib.LTIdsToAscii.restype = ctypes.c_int32
      lib.LTIdsToAscii.argtypes = [
          ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_char_p,
          ctypes.c_int32
      ]
      lib.LTVocabLoad.restype = ctypes.c_void_p
      lib.LTVocabLoad.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
      lib.LTVocabFree.argtypes = [ctypes.c_void_p]
      lib.LTVocabSize.restype = ctypes.c_int32
      lib.LTVocabSize.argtypes = [ctypes.c_void_p]
      lib.LTVocabToIds.restype = ctypes.c_int32
      lib.LTVocabToIds.argtypes = [
          ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
          ctypes.POINTER(ctypes.c_int32), ctypes.c_int32
      ]
      for prefix in ("LTWpm", "LTBpe"):
        load = getattr(lib, prefix + "Load")
        load.restype = ctypes.c_void_p
        load.argtypes = ([ctypes.c_char_p, ctypes.c_char_p] if prefix ==
                         "LTWpm" else
                         [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p])
        getattr(lib, prefix + "Free").argtypes = [ctypes.c_void_p]
        size = getattr(lib, prefix + "Size")
        size.restype = ctypes.c_int32
        size.argtypes = [ctypes.c_void_p]
        enc = getattr(lib, prefix + "Encode")
        enc.restype = ctypes.c_int32
        enc.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32
        ]
        dec = getattr(lib, prefix + "Decode")
        dec.restype = ctypes.c_int32
        dec.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int32
        ]
      lib.LTVocabToText.restype = ctypes.c_int32
      lib.LTVocabToText.argtypes = [
          ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
          ctypes.c_char_p, ctypes.c_int32
      ]
      _LIB = lib
  return _LIB


class RecordYielder:
  """Streams shuffled records from sharded files (C++ threads)."""

  def __init__(self, file_pattern: str, seed: int = 301,
               shuffle_buffer_size: int = 10000, num_threads: int = 2,
               max_epochs: int = 0, shuffle: bool = True,
               shard_index: int = 0, num_shards: int = 1,
               max_record_bytes: int = 1 << 20):
    self._lib = Lib()
    self._handle = self._lib.LTYielderNew(
        file_pattern.encode(), seed, shuffle_buffer_size, num_threads,
        max_epochs, int(shuffle), shard_index, num_shards)
    if not self._handle:
      raise ValueError(
          f"RecordYielder: no files match {file_pattern!r} (or unknown "
          "type prefix; known: text/tfrecord/recordio/iota)")
    self._buf = ctypes.create_string_buffer(max_record_bytes)

  def Next(self) -> bytes | None:
    """Returns the next record, or None when the stream is exhausted."""
    src = ctypes.c_int32(0)
    n = self._lib.LTYielderNext(self._handle, self._buf,
                                len(self._buf), ctypes.byref(src))
    if n < 0:
      return None
    if n > len(self._buf):
      # record stayed pending C-side; retry with a bigger buffer (lossless)
      self._buf = ctypes.create_string_buffer(int(n))
      return self.Next()
    return ctypes.string_at(self._buf, n)

  @property
  def epochs_completed(self) -> int:
    return self._lib.LTYielderEpochs(self._handle)

  def __iter__(self):
    while True:
      rec = self.Next()
      if rec is None:
        return
      yield rec

  def Close(self):
    if self._handle:
      self._lib.LTYielderFree(self._handle)
      self._handle = None

  def __del__(self):
    try:
      self.Close()
    except Exception:
      pass


def PackSequences(lens, num_rows: int, time: int,
                  spread_first_n: int = 0):
  """Best-fit packing: returns (row[n], offset[n]); row -1 = dropped.

  spread_first_n is reserved for reference-parity spreading and currently
  ignored by the native implementation.
  """
  lib = Lib()
  lens = np.ascontiguousarray(lens, np.int32)
  n = len(lens)
  row = np.empty(n, np.int32)
  off = np.empty(n, np.int32)
  lib.LTPackSequences(
      lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n, num_rows, time,
      row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
      off.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), spread_first_n)
  return row, off


def ApplyPacking(sequences, row, offset, num_rows, time, pad_value=0,
                 extra_payloads=None, return_used=False):
  """Materializes packed ids/segment_ids/segment_pos from an assignment.

  `extra_payloads`: optional dict {name: list-of-arrays} packed with the same
  assignment (e.g. labels alongside ids); returned as a dict after seg_pos.
  `return_used`: also return the list of sequence indices that were placed
  (row >= 0) — callers keeping a pending pool drop exactly these.
  """
  ids = np.full((num_rows, time), pad_value, np.int32)
  extras = {name: np.full((num_rows, time), pad_value, np.int32)
            for name in (extra_payloads or {})}
  seg_ids = np.zeros((num_rows, time), np.int32)
  seg_pos = np.zeros((num_rows, time), np.int32)
  seg_counter = np.zeros(num_rows, np.int32)
  used = []
  for i, seq in enumerate(sequences):
    r = int(row[i])
    if r < 0:
      continue
    o = int(offset[i])
    L = len(seq)
    ids[r, o:o + L] = seq
    for name, payload in (extra_payloads or {}).items():
      extras[name][r, o:o + L] = payload[i][:L]
    seg_counter[r] += 1
    seg_ids[r, o:o + L] = seg_counter[r]
    seg_pos[r, o:o + L] = np.arange(L)
    used.append(i)
  out = (ids, seg_ids, seg_pos)
  if extra_payloads is not None:
    out = out + (extras,)
  if return_used:
    out = out + (used,)
  return out


class AsciiTokenizer:
  """Char-level tokenizer (ref ascii_tokenizer.cc id space)."""

  vocab_size = 76
  sos_id, eos_id, unk_id = 0, 1, 73

  def StringsToIds(self, texts, max_len: int, append_eos: bool = True):
    lib = Lib()
    b = len(texts)
    ids = np.zeros((b, max_len), np.int32)
    lens = np.zeros(b, np.int32)
    for i, text in enumerate(texts):
      data = text.encode() if isinstance(text, str) else bytes(text)
      out = np.zeros(max_len, np.int32)
      n = lib.LTAsciiToIds(data, len(data),
                           out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                           max_len, int(append_eos))
      ids[i, :n] = out[:n]
      lens[i] = n
    paddings = (np.arange(max_len)[None, :] >= lens[:, None]).astype(
        np.float32)
    return ids, paddings

  def IdsToStrings(self, ids, lens=None):
    lib = Lib()
    out = []
    for i in range(len(ids)):
      row = np.ascontiguousarray(ids[i], np.int32)
      n = int(lens[i]) if lens is not None else len(row)
      buf = ctypes.create_string_buffer(4 * max(n, 1))
      m = lib.LTIdsToAscii(
          row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n, buf,
          len(buf))
      out.append(buf.raw[:m].decode("utf-8", errors="replace"))
    return out


class VocabTokenizer:
  """Whitespace-token vocab lookup (ref simple_vocab.cc)."""

  def __init__(self, vocab_path: str, unk_token: str = "<unk>"):
    self._lib = Lib()
    self._handle = self._lib.LTVocabLoad(vocab_path.encode(),
                                         unk_token.encode())
    if not self._handle:
      raise FileNotFoundError(vocab_path)

  @property
  def vocab_size(self) -> int:
    return self._lib.LTVocabSize(self._handle)

  def StringsToIds(self, texts, max_len: int):
    b = len(texts)
    ids = np.zeros((b, max_len), np.int32)
    lens = np.zeros(b, np.int32)
    for i, text in enumerate(texts):
      data = text.encode() if isinstance(text, str) else bytes(text)
      out = np.zeros(max_len, np.int32)
      n = self._lib.LTVocabToIds(
          self._handle, data, len(data),
          out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), max_len)
      ids[i, :n] = out[:n]
      lens[i] = n
    paddings = (np.arange(max_len)[None, :] >= lens[:, None]).astype(
        np.float32)
    return ids, paddings

  def IdsToStrings(self, ids, lens=None):
    out = []
    for i in range(len(ids)):
      row = np.ascontiguousarray(ids[i], np.int32)
      n = int(lens[i]) if lens is not None else len(row)
      buf = ctypes.create_string_buffer(64 * max(n, 1))
      m = self._lib.LTVocabToText(
          self._handle, row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
          n, buf, len(buf))
      out.append(buf.raw[:m].decode("utf-8", errors="replace"))
    return out

  def __del__(self):
    try:
      if self._handle:
        self._lib.LTVocabFree(self._handle)
    except Exception:
      pass

class _SubwordTokenizerBase:
  """Shared encode/decode surface for the C++ subword tokenizers."""

  _PREFIX = ""

  def __init__(self):
    self._lib = Lib()
    self._handle = None

  def _Fn(self, name):
    return getattr(self._lib, self._PREFIX + name)

  @property
  def vocab_size(self) -> int:
    return self._Fn("Size")(self._handle)

  def StringsToIds(self, texts, max_len: int):
    b = len(texts)
    ids = np.zeros((b, max_len), np.int32)
    lens = np.zeros(b, np.int32)
    for i, text in enumerate(texts):
      data = text.encode() if isinstance(text, str) else bytes(text)
      out = np.zeros(max_len, np.int32)
      n = self._Fn("Encode")(
          self._handle, data, len(data),
          out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), max_len)
      ids[i, :n] = out[:n]
      lens[i] = n
    paddings = (np.arange(max_len)[None, :] >= lens[:, None]).astype(
        np.float32)
    return ids, paddings

  def IdsToStrings(self, ids, lens=None):
    out = []
    for i in range(len(ids)):
      row = np.ascontiguousarray(ids[i], np.int32)
      n = int(lens[i]) if lens is not None else len(row)
      buf = ctypes.create_string_buffer(64 * max(n, 1))
      m = self._Fn("Decode")(
          self._handle, row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
          n, buf, len(buf))
      out.append(buf.raw[:m].decode("utf-8", errors="replace"))
    return out

  def __del__(self):
    try:
      if self._handle:
        self._Fn("Free")(self._handle)
    except Exception:
      pass


class WpmTokenizer(_SubwordTokenizerBase):
  """Greedy longest-match wordpiece (ref wpm_encoder.py semantics).

  Auto-detects the marker convention from the vocab file: sentencepiece
  word-start "▁" or BERT continuation "##".
  """

  _PREFIX = "LTWpm"

  def __init__(self, vocab_path: str, unk_token: str = "<unk>"):
    super().__init__()
    self._handle = self._lib.LTWpmLoad(vocab_path.encode(),
                                       unk_token.encode())
    if not self._handle:
      raise FileNotFoundError(vocab_path)


class BpeTokenizer(_SubwordTokenizerBase):
  """Merge-ops BPE (ref BpeWordsToIds kernel semantics: codes file of merge
  operations in priority order + subword vocab file, "</w>" end-of-word)."""

  _PREFIX = "LTBpe"

  def __init__(self, codes_path: str, vocab_path: str,
               unk_token: str = "<unk>"):
    super().__init__()
    self._handle = self._lib.LTBpeLoad(codes_path.encode(),
                                       vocab_path.encode(),
                                       unk_token.encode())
    if not self._handle:
      raise FileNotFoundError(f"{codes_path} / {vocab_path}")
