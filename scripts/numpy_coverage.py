"""NumPy API coverage table generator (reference: ``scripts/`` numpy-coverage
tooling, SURVEY §2.6).

Walks numpy's public callable surface, checks which names ``heat_tpu``
exposes, and prints a markdown table plus summary counts.  Run:

    python scripts/numpy_coverage.py            # summary + missing list
    python scripts/numpy_coverage.py --table    # full markdown table
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the table is a static-API artifact — never touch an accelerator for it
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import heat_tpu as ht  # noqa: E402

# numpy names that are intentionally out of scope (deprecated aliases,
# printing/dtype plumbing, financial functions removed upstream, …)
SKIP = {
    "add_docstring", "add_newdoc", "asanyarray", "asarray_chkfinite",
    "asmatrix", "base_repr", "binary_repr", "block", "bmat", "byte_bounds",
    "common_type", "deprecate", "deprecate_with_doc", "disp", "fastCopyAndTranspose",
    "format_float_positional", "format_float_scientific", "from_dlpack",
    "frombuffer", "fromfile", "fromfunction", "fromiter", "frompyfunc",
    "fromregex", "fromstring", "genfromtxt", "get_array_wrap", "get_include",
    "get_printoptions", "getbufsize", "geterr", "geterrcall", "geterrobj",
    "info", "is_busday", "isfortran", "issctype", "issubclass_", "issubdtype",
    "issubsctype", "iterable", "lookfor", "mafromtxt", "maximum_sctype",
    "may_share_memory", "memmap", "min_scalar_type", "mintypecode", "msort",
    "ndfromtxt", "nested_iters", "obj2sctype", "printoptions", "recfromcsv",
    "recfromtxt", "require", "safe_eval", "savez", "savez_compressed",
    "sctype2char", "set_numeric_ops", "set_printoptions", "set_string_function",
    "setbufsize", "seterr", "seterrcall", "seterrobj", "shares_memory",
    "show_config", "show_runtime", "source", "typename", "who", "test", "isnat",
    "busday_count", "busday_offset", "datetime_as_string", "datetime_data",
    "loadtxt", "savetxt", "packbits", "unpackbits", "poly", "polyadd",
    "polyder", "polydiv", "polyfit", "polyint", "polymul", "polysub",
    "polyval", "roots", "find_common_type", "get_array_api_strict_flags",
}


def coverage():
    rows = []
    for name in sorted(dir(np)):
        if name.startswith("_") or name in SKIP:
            continue
        obj = getattr(np, name)
        if not callable(obj) or isinstance(obj, type):
            continue
        rows.append((name, hasattr(ht, name)))
    return rows


def main() -> None:
    rows = coverage()
    have = [n for n, ok in rows if ok]
    miss = [n for n, ok in rows if not ok]
    if "--table" in sys.argv:
        print("| numpy function | heat_tpu |")
        print("|---|---|")
        for name, ok in rows:
            print(f"| `{name}` | {'✓' if ok else '—'} |")
        print()
    print(f"covered {len(have)}/{len(rows)} "
          f"({100.0 * len(have) / max(len(rows), 1):.1f}%) of numpy's "
          "in-scope callable surface")
    if miss:
        print("missing:", ", ".join(miss))


if __name__ == "__main__":
    main()
