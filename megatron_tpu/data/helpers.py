"""Dataset index builders: native module loader + Python fallbacks.

The reference builds megatron/data/helpers.cpp with a Makefile or a runtime
compile_helper() (megatron/data/dataset_utils.py:82-92); this does the same
with g++ against the CPython/NumPy headers (no pybind11 in the toolchain).
The numpy/Python fallbacks below define the semantics and are tested to
match the native module exactly.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig
import warnings
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "_helpers.cpp")
_native = None
_native_tried = False
#: how the module in use came to be: "built" (compiled by this process),
#: "loaded" (a binary of THIS source, compiled earlier on this checkout)
#: or "python" (no native build; the reference implementations below)
_native_origin = "python"


def _build_native() -> Tuple[object, str]:
    """(module, "built" | "loaded")."""
    # the binary's name carries a hash of the source it was built from, so
    # a stale or foreign .so lying in the tree (git ignores *.so, a copied
    # checkout may not) is never loaded in place of the committed source
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = os.path.join(_HERE, f"_helpers_native_{digest}{ext}")
    origin = "loaded"
    if not os.path.exists(out):
        py_inc = sysconfig.get_paths()["include"]
        np_inc = np.get_include()
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            f"-I{py_inc}", f"-I{np_inc}", _SRC, "-o", tmp,
        ]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)  # concurrent builders each publish a whole file
        origin = "built"
    import importlib.util

    spec = importlib.util.spec_from_file_location("_helpers_native", out)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, origin


def native_helpers() -> Optional[object]:
    """The compiled module, building it on first use; None if unavailable."""
    global _native, _native_tried, _native_origin
    if not _native_tried:
        _native_tried = True
        try:
            _native, _native_origin = _build_native()
        except Exception as e:  # noqa: BLE001 - no compiler, bad env,
            # cffi quirks: anything here means "no native build" — fall
            # back to the numpy reference implementations (warned)
            warnings.warn(f"native dataset helpers unavailable ({e}); "
                          "using slower Python fallbacks")
            _native = None
    return _native


def native_origin() -> str:
    """"built" | "loaded" | "python" — which index builders this process
    runs (see _native_origin); resolves the module on first call."""
    native_helpers()
    return _native_origin


# ---------------------------------------------------------------------------
# Python reference implementations (semantics source of truth)
# ---------------------------------------------------------------------------


def _py_build_sample_idx(sizes: np.ndarray, doc_idx: np.ndarray,
                         seq_length: int, num_epochs: int,
                         tokens_per_epoch: int) -> np.ndarray:
    total_tokens = num_epochs * tokens_per_epoch
    num_samples = (total_tokens - 1) // seq_length
    sample_idx = np.zeros((num_samples + 1, 2), np.int32)
    doc_pos, offset = 0, 0
    for i in range(1, num_samples + 1):
        remaining = seq_length
        while remaining > 0:
            doc_len = sizes[doc_idx[doc_pos]] - offset
            if doc_len > remaining:
                offset += remaining
                remaining = 0
            else:
                remaining -= doc_len
                doc_pos += 1
                offset = 0
        sample_idx[i] = (doc_pos, offset)
    return sample_idx


def _py_build_blending_indices(dataset_index: np.ndarray,
                               dataset_sample_index: np.ndarray,
                               weights: np.ndarray, num_datasets: int,
                               size: int, verbose: bool) -> None:
    current = np.zeros(num_datasets, np.int64)
    for i in range(size):
        errors = weights * (i + 1) - current
        d = int(np.argmax(errors))
        dataset_index[i] = d
        dataset_sample_index[i] = current[d]
        current[d] += 1


def build_sample_idx(sizes: np.ndarray, doc_idx: np.ndarray, seq_length: int,
                     num_epochs: int, tokens_per_epoch: int) -> np.ndarray:
    sizes = np.ascontiguousarray(sizes, np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, np.int32)
    mod = native_helpers()
    if mod is not None:
        return mod.build_sample_idx(sizes, doc_idx, int(seq_length),
                                    int(num_epochs), int(tokens_per_epoch))
    return _py_build_sample_idx(sizes, doc_idx, seq_length, num_epochs,
                                tokens_per_epoch)


def build_blending_indices(weights: np.ndarray, size: int,
                           verbose: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    weights = np.ascontiguousarray(weights, np.float64)
    dataset_index = np.zeros(size, np.uint8)
    dataset_sample_index = np.zeros(size, np.int64)
    mod = native_helpers()
    if mod is not None:
        mod.build_blending_indices(dataset_index, dataset_sample_index,
                                   weights, len(weights), int(size),
                                   int(verbose))
    else:
        _py_build_blending_indices(dataset_index, dataset_sample_index,
                                   weights, len(weights), size, verbose)
    return dataset_index, dataset_sample_index


def build_mapping(docs: np.ndarray, sizes: np.ndarray, num_epochs: int,
                  max_num_samples: int, max_seq_length: int,
                  short_seq_prob: float, seed: int, verbose: bool = False,
                  min_num_sent: int = 2) -> np.ndarray:
    """BERT sentence-pair map; native-only (the Python loop would be
    impractically slow and this path is exercised only by BERT data prep)."""
    mod = native_helpers()
    if mod is None:
        raise RuntimeError("build_mapping requires the native helpers module")
    return mod.build_mapping(
        np.ascontiguousarray(docs, np.int64),
        np.ascontiguousarray(sizes, np.int32),
        int(num_epochs), int(max_num_samples), int(max_seq_length),
        float(short_seq_prob), int(seed), int(verbose), int(min_num_sent))


def build_blocks_mapping(docs: np.ndarray, sizes: np.ndarray,
                         titles: np.ndarray, num_epochs: int,
                         max_num_samples: int, max_seq_length: int,
                         seed: int, verbose: bool = False,
                         use_one_sent_blocks: bool = False) -> np.ndarray:
    mod = native_helpers()
    if mod is None:
        raise RuntimeError("build_blocks_mapping requires the native helpers module")
    return mod.build_blocks_mapping(
        np.ascontiguousarray(docs, np.int64),
        np.ascontiguousarray(sizes, np.int32),
        np.ascontiguousarray(titles, np.int32),
        int(num_epochs), int(max_num_samples), int(max_seq_length),
        int(seed), int(verbose), int(use_one_sent_blocks))
