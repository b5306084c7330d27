# Copyright 2026 The rayfed-tpu Authors.
#
# Licensed under the Apache License, Version 2.0 (the "License");
# you may not use this file except in compliance with the License.
# You may obtain a copy of the License at
#
#     http://www.apache.org/licenses/LICENSE-2.0
#
# Unless required by applicable law or agreed to in writing, software
# distributed under the License is distributed on an "AS IS" BASIS,
# WITHOUT WARRANTIES OR CONDITIONS OF ANY KIND, either express or implied.
# See the License for the specific language governing permissions and
# limitations under the License.

"""Shared utilities: dependency resolution, logging, address validation.

Capability parity: reference ``fed/utils.py`` — ``resolve_dependencies``
(48-83), ``setup_logger`` (99-146), address validation (198-239).
"""

from __future__ import annotations

import logging
import os
import re
from typing import Any, Dict, Tuple

from rayfed_tpu import tree_util
from rayfed_tpu.fed_object import FedObject

logger = logging.getLogger(__name__)


def resolve_dependencies(
    current_party: str, current_fed_task_id: int, *args, **kwargs
) -> Tuple[tuple, dict]:
    """Replace every ``FedObject`` in the argument pytree with a value future.

    Own-party objects yield their live future; foreign objects yield a
    ``recv`` future parked on the (producer id, this consumer id) rendezvous,
    cached on the handle so repeated consumption does not re-receive
    (ref ``fed/utils.py:48-83``).
    """
    flattened_args, tree_spec = tree_util.tree_flatten((args, kwargs))
    indexes = []
    resolved = []
    for idx, arg in enumerate(flattened_args):
        if isinstance(arg, FedObject):
            indexes.append(idx)
            if arg.get_party() == current_party:
                resolved.append(arg.get_value_future())
            else:
                fut = arg.get_value_future()
                if fut is None:
                    from rayfed_tpu.proxy.barriers import recv

                    fut = recv(
                        current_party,
                        arg.get_party(),
                        arg.get_fed_task_id(),
                        current_fed_task_id,
                    )
                    arg._cache_value_future(fut)
                resolved.append(fut)
    if indexes:
        for idx, actual_val in zip(indexes, resolved):
            flattened_args[idx] = actual_val
    args, kwargs = tree_util.tree_unflatten(flattened_args, tree_spec)
    return args, kwargs


class _ContextFilter(logging.Filter):
    """Injects party / job name into every record
    (ref ``fed/utils.py:99-146``, format ``constants.py:30``)."""

    def __init__(self, party: str, job_name: str):
        super().__init__()
        self._party = party
        self._job_name = job_name

    def filter(self, record: logging.LogRecord) -> bool:
        record.party = self._party
        record.jobname = self._job_name
        return True


def setup_logger(
    logging_level,
    logging_format: str,
    date_format: str = "%Y-%m-%d %H:%M:%S",
    party_val: str = "",
    job_name: str = "",
) -> None:
    root = logging.getLogger()
    if isinstance(logging_level, str):
        logging_level = getattr(logging, logging_level.upper())
    root.setLevel(logging_level)
    # Replace our previous handler if re-initialized (repeat init tests).
    for h in list(root.handlers):
        if getattr(h, "_fedtpu_handler", False):
            root.removeHandler(h)
    handler = logging.StreamHandler()
    handler._fedtpu_handler = True  # type: ignore[attr-defined]
    handler.setFormatter(logging.Formatter(logging_format, datefmt=date_format))
    handler.addFilter(_ContextFilter(party_val, job_name))
    root.addHandler(handler)


_ADDR_RE = re.compile(r"^(?P<host>[^:/ ]+):(?P<port>\d{1,5})$")


def is_tpu_backend() -> bool:
    """True when jax's active backend is a TPU. Every TPU-vs-interpret
    gate in the package uses this, so the Pallas kernels, the remat
    defaults and the dense CPU reference all switch on one test."""
    import jax

    return jax.default_backend() == "tpu"


#: The checkout this package was imported from — home of the default
#: persistent compilation cache (see :func:`enable_compilation_cache`).
_CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compilation_cache(default_subdir: str = ".jax_cache") -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it as
    the cache directory and this function sets no other. Where it is
    not, the cache lives at a fixed path inside the checkout
    (``<checkout>/<default_subdir>``, git-ignored): the path is part of
    the cache key, so a directory made from ``tempfile``, a pid or the
    time never hits. Every process of one run (bench children, the
    smoke's party processes, the tests) calls this, so they share it.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT_ROOT, default_subdir)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Every program, however fast it compiled: with a threshold, a
    # program that compiles in about that long is written or not by the
    # noise of one compilation, and a warm start re-compiles it or does
    # not (the engine's chunk programs compile in 0.9-1.4 s each).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def parse_address(address: str) -> "tuple[str, int]":
    """Split a validated ``host:port`` into its parts — the one place
    the accepted address format is interpreted (transports and the
    readiness probe must agree on it)."""
    host, port = address.rsplit(":", 1)
    return host, int(port)


def validate_address(address: str) -> None:
    """Accept ``host:port`` or ``hostname:port``; reject schemes and
    malformed ports (behavioral contract of ref ``fed/utils.py:198-239``,
    tested by ``fed/tests/without_ray_tests/test_utils.py``)."""
    if not isinstance(address, str):
        raise ValueError(f"address must be a string, got {type(address)}")
    m = _ADDR_RE.match(address)
    if not m:
        raise ValueError(
            f"Invalid address '{address}': expected 'host:port' "
            "with no URL scheme."
        )
    port = int(m.group("port"))
    if not 0 < port < 65536:
        raise ValueError(f"Invalid port in address '{address}'.")


def validate_addresses(addresses: Dict[str, Any]) -> None:
    if not isinstance(addresses, dict) or not addresses:
        raise ValueError("addresses must be a non-empty {party: 'host:port'} dict")
    for party, addr in addresses.items():
        if not isinstance(party, str) or not party:
            raise ValueError(f"party name must be a non-empty string, got {party!r}")
        validate_address(addr)


def dict2tuple(dic: Dict) -> tuple:
    """Stable tuple form of a dict for hashing/logging
    (ref ``fed/utils.py:182-195``)."""
    if dic is None:
        return ()
    return tuple(sorted(dic.items()))
