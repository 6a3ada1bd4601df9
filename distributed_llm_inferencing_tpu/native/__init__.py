"""Native runtime layer: C++ KV-block pool + radix prefix cache via ctypes.

The reference's native layer was vendored torch/CUDA behind HF ``generate``
(SURVEY.md §2.5). Here the device compute is XLA/Pallas and the *host-side*
runtime — the allocator deciding which paged-KV HBM blocks each sequence
owns, with ref-counted radix prefix sharing — is C++
(native/src/block_pool.cc), compiled on first use with g++ and bound through
a minimal C ABI (no pybind11 in this image).

``BlockPool`` is the Python facade. A library that cannot be built or
loaded is an error (``NativeBuildError``), not a quiet change of
allocator: the pure-Python pool with identical semantics exists only as
the differential oracle tests ask for (``force_python=True``);
``BlockPool.is_native`` reports which one is live.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import threading
import time
from typing import List, Optional, Sequence, Tuple

log = logging.getLogger("dli.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "block_pool.cc")
_LIB = os.path.join(_HERE, "libdli_native.so")
_build_lock = threading.Lock()
_lib = None
# seconds g++ took in THIS process; None when an up-to-date library was
# found on disk (chip_smoke.py reports it: a clean checkout must build)
build_seconds: Optional[float] = None


class NativeBuildError(RuntimeError):
    """The C++ block pool could not be compiled or loaded."""


def _build() -> str:
    """Compile the shared library if missing or stale; return its path.

    The compile lands in a temp file and is os.rename()d into place so a
    concurrent process (master + worker on one host) never dlopens a
    half-written library.
    """
    global build_seconds
    if (os.path.exists(_LIB)
            and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)):
        return _LIB
    t0 = time.monotonic()
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", _SRC,
             "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.rename(tmp, _LIB)  # atomic on POSIX
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            "native block_pool build failed:\n"
            + e.stderr.decode(errors="replace")[-2000:]) from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(
            f"native block_pool build failed: {e!r}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    build_seconds = time.monotonic() - t0
    return _LIB


def _load():
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        path = _build()
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            raise NativeBuildError(f"failed to load {path}: {e}") from e
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.dli_pool_create.restype = ctypes.c_void_p
        lib.dli_pool_create.argtypes = [ctypes.c_int32, ctypes.c_int32]
        lib.dli_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.dli_pool_free_count.restype = ctypes.c_int32
        lib.dli_pool_free_count.argtypes = [ctypes.c_void_p]
        lib.dli_pool_alloc.restype = ctypes.c_int32
        lib.dli_pool_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int32, i32p]
        lib.dli_pool_ref.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.dli_pool_unref.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int32]
        lib.dli_pool_match.restype = ctypes.c_int32
        lib.dli_pool_match.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int32,
                                       i32p]
        lib.dli_pool_insert.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int32,
                                        i32p, ctypes.c_int32]
        lib.dli_pool_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int64)]
        lib.dli_pool_refcount.restype = ctypes.c_int32
        lib.dli_pool_refcount.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.dli_pool_set_evict_log.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int32]
        lib.dli_pool_evict_pop.restype = ctypes.c_int32
        lib.dli_pool_evict_pop.argtypes = [ctypes.c_void_p, i32p, i32p,
                                           ctypes.c_int32]
        _lib = lib
        return _lib


def _arr(vals: Sequence[int]):
    return (ctypes.c_int32 * len(vals))(*vals)


class BlockPool:
    """Paged-KV block allocator with radix prefix cache.

    API (block ids are ints in [0, num_blocks)):
      - alloc(n) -> list of n fresh block ids (refcount 1), or None if the
        pool is exhausted even after evicting unreferenced cached blocks.
      - release(blocks): drop one reference per block (freeing or returning
        to the prefix cache's evictable set).
      - match_prefix(tokens) -> (blocks, n_tokens): longest cached prefix in
        whole blocks; caller receives one reference per returned block.
      - insert_prefix(tokens, blocks, skip): register freshly-filled blocks
        for tokens' prefix; `skip` = leading blocks already cached.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 force_python: bool = False):
        if num_blocks < 1 or block_size < 1:
            raise ValueError("num_blocks and block_size must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._lock = threading.Lock()
        # eviction hook (runtime/kvtier.py host-offload tier): called with
        # [(block_id, full_token_chain), ...] after any alloc() that
        # evicted cached blocks — while their device KV is still resident
        self._evict_hook = None
        lib = None if force_python else _load()
        self._lib = lib
        if lib is not None:
            self._pool = ctypes.c_void_p(
                lib.dli_pool_create(num_blocks, block_size))
        else:
            self._py = _PyPool(num_blocks, block_size)

    @property
    def is_native(self) -> bool:
        return self._lib is not None

    def __del__(self):
        lib = getattr(self, "_lib", None)
        pool = getattr(self, "_pool", None)
        if lib is not None and pool:
            lib.dli_pool_destroy(pool)
            self._pool = None

    def _check_blocks(self, blocks: Sequence[int]) -> List[int]:
        blocks = [int(b) for b in blocks]
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"block id {b} out of range "
                                 f"[0, {self.num_blocks})")
        return blocks

    # ---- allocation ---------------------------------------------------

    def free_count(self) -> int:
        with self._lock:
            if self._lib:
                return self._lib.dli_pool_free_count(self._pool)
            return self._py.free_count()

    def set_evict_hook(self, fn) -> None:
        """Register ``fn(evictions)`` — ``evictions`` is a list of
        ``(block_id, token_chain)`` for radix blocks the pool evicted to
        satisfy an alloc. Called OUTSIDE the pool lock, after the alloc
        that triggered the evictions returns, but before the caller can
        dispatch any program that overwrites the block — the window in
        which the block's device KV is still intact and can be copied to
        the host arena. ``None`` unregisters."""
        with self._lock:
            self._evict_hook = fn
            cap = self.num_blocks if fn is not None else 0
            if self._lib:
                self._lib.dli_pool_set_evict_log(self._pool, cap)
            else:
                self._py.set_evict_log(cap)

    def _drain_evictions(self) -> list:
        """Collect logged evictions (caller holds the lock)."""
        if self._lib:
            out = []
            blk = ctypes.c_int32()
            toks = (ctypes.c_int32 * (self.num_blocks * self.block_size))()
            while True:
                n = self._lib.dli_pool_evict_pop(
                    self._pool, ctypes.byref(blk), toks, len(toks))
                if n < 0:
                    break
                out.append((int(blk.value), list(toks[:n])))
            return out
        return self._py.drain_evictions()

    def alloc(self, n: int) -> Optional[List[int]]:
        if n == 0:
            return []
        with self._lock:
            if self._lib:
                out = (ctypes.c_int32 * n)()
                ok = self._lib.dli_pool_alloc(self._pool, n, out)
                got = list(out) if ok else None
            else:
                got = self._py.alloc(n)
            hook = self._evict_hook
            evicted = self._drain_evictions() if hook is not None else []
        if evicted and hook is not None:
            try:
                hook(evicted)
            except Exception:
                # the hook is an opportunistic offload: a failure loses
                # that copy, nothing more. Raising here would propagate
                # out of alloc() AFTER the blocks were handed out — the
                # caller never learns the ids, leaking them forever.
                log.exception("evict hook failed; evictions not offloaded")
        return got

    def release(self, blocks: Sequence[int]) -> None:
        if not blocks:
            return
        blocks = self._check_blocks(blocks)
        with self._lock:
            if self._lib:
                a = _arr(blocks)
                self._lib.dli_pool_unref(self._pool, a, len(blocks))
            else:
                self._py.release(blocks)

    def refcount(self, block: int) -> int:
        [block] = self._check_blocks([block])
        with self._lock:
            if self._lib:
                return self._lib.dli_pool_refcount(self._pool, block)
            return self._py.refcount[block]

    # ---- prefix cache -------------------------------------------------

    def match_prefix(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        with self._lock:
            if self._lib:
                cap = len(tokens) // self.block_size
                out = (ctypes.c_int32 * max(cap, 1))()
                t = _arr(list(tokens))
                n = self._lib.dli_pool_match(self._pool, t, len(tokens), out)
                blocks = list(out[:n])
            else:
                blocks = self._py.match(tokens)
            return blocks, len(blocks) * self.block_size

    def insert_prefix(self, tokens: Sequence[int], blocks: Sequence[int],
                      skip: int) -> None:
        blocks = self._check_blocks(blocks)
        need = len(tokens) // self.block_size - skip
        if need <= 0:
            return
        if len(blocks) < need:
            raise ValueError(
                f"insert_prefix needs {need} blocks for "
                f"{len(tokens)} tokens with skip={skip}, got {len(blocks)}")
        with self._lock:
            if self._lib:
                t = _arr(list(tokens))
                b = _arr(blocks)
                self._lib.dli_pool_insert(self._pool, t, len(tokens), b, skip)
            else:
                self._py.insert(tokens, blocks, skip)

    def stats(self) -> dict:
        with self._lock:
            if self._lib:
                out = (ctypes.c_int64 * 3)()
                self._lib.dli_pool_stats(self._pool, out)
                hits, misses, evictions = out
            else:
                hits, misses = self._py.hits, self._py.misses
                evictions = self._py.evictions
            return {"prefix_hits": int(hits), "prefix_misses": int(misses),
                    "evictions": int(evictions),
                    "native": self._lib is not None}


class _PyNode:
    __slots__ = ("tokens", "block", "parent", "children", "last_use",
                 "in_evictable")

    def __init__(self, tokens=(), block=-1, parent=None):
        self.tokens = tokens
        self.block = block
        self.parent = parent
        self.children = {}
        self.last_use = 0
        self.in_evictable = False


class _PyPool:
    """Pure-Python mirror of the C++ pool (same semantics — including the
    evictable-leaf LRU index — serving as fallback and as the
    differential-testing oracle in tests/test_native_pool.py)."""

    def __init__(self, num_blocks: int, block_size: int):
        self.block_size = block_size
        self.free_list = list(range(num_blocks))
        self.refcount = [0] * num_blocks
        self.root = _PyNode()
        self.block_node = {}          # block -> _PyNode
        self.evictable = set()        # (last_use, block)
        self.clock = 0
        self.hits = self.misses = self.evictions = 0
        self.evict_log_cap = 0
        self.evict_log = []           # (block, full token chain)

    def set_evict_log(self, cap: int):
        self.evict_log_cap = cap
        if cap <= 0:
            self.evict_log.clear()

    def drain_evictions(self):
        out, self.evict_log = self.evict_log, []
        return out

    def free_count(self):
        return len(self.free_list)

    def _evictable_add(self, n):
        if (not n.in_evictable and n is not self.root and not n.children
                and n.block >= 0 and self.refcount[n.block] == 0):
            self.evictable.add((n.last_use, n.block))
            n.in_evictable = True

    def _evictable_remove(self, n):
        if n.in_evictable:
            self.evictable.discard((n.last_use, n.block))
            n.in_evictable = False

    def _touch(self, n):
        was = n.in_evictable
        if was:
            self._evictable_remove(n)
        n.last_use = self.clock
        if was:
            self._evictable_add(n)

    def _evict_one(self) -> bool:
        if not self.evictable:
            return False
        key = min(self.evictable)
        victim = self.block_node[key[1]]
        if self.evict_log_cap > 0:
            chain, node = [], victim
            while node is not None and node.parent is not None:
                chain.append(node.tokens)
                node = node.parent
            flat = [t for toks in reversed(chain) for t in toks]
            self.evict_log.append((victim.block, flat))
            if len(self.evict_log) > self.evict_log_cap:
                self.evict_log.pop(0)
        self.evictable.discard(key)
        victim.in_evictable = False
        self.free_list.append(victim.block)
        del self.block_node[victim.block]
        self.evictions += 1
        del victim.parent.children[victim.tokens]
        self._evictable_add(victim.parent)
        return True

    def alloc(self, n):
        while len(self.free_list) < n:
            if not self._evict_one():
                return None
        out = []
        for _ in range(n):
            b = self.free_list.pop(0)
            self.refcount[b] = 1
            out.append(b)
        return out

    def _ref(self, block):
        self.refcount[block] += 1
        if block in self.block_node:
            self._evictable_remove(self.block_node[block])

    def release(self, blocks):
        for b in blocks:
            if self.refcount[b] > 0:
                self.refcount[b] -= 1
                if self.refcount[b] == 0:
                    if b not in self.block_node:
                        self.free_list.append(b)
                    else:
                        self._evictable_add(self.block_node[b])

    def match(self, tokens):
        bs = self.block_size
        cur = self.root
        self.clock += 1
        out = []
        for i in range(len(tokens) // bs):
            key = tuple(tokens[i * bs:(i + 1) * bs])
            child = cur.children.get(key)
            if child is None:
                break
            cur = child
            self._touch(cur)
            out.append(cur.block)
            self._ref(cur.block)
        if out:
            self.hits += 1
        else:
            self.misses += 1
        return out

    def insert(self, tokens, blocks, skip):
        bs = self.block_size
        cur = self.root
        self.clock += 1
        for i in range(len(tokens) // bs):
            key = tuple(tokens[i * bs:(i + 1) * bs])
            child = cur.children.get(key)
            if child is not None:
                cur = child
                self._touch(cur)
                continue
            if i < skip:
                break
            node = _PyNode(key, blocks[i - skip], cur)
            node.last_use = self.clock
            self.block_node[node.block] = node
            self._evictable_remove(cur)
            cur.children[key] = node
            cur = node
