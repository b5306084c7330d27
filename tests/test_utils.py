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

"""Address validation and misc utils (mirror of ref
``fed/tests/without_ray_tests/test_utils.py``)."""

import pytest

from rayfed_tpu.utils import dict2tuple, validate_address, validate_addresses


@pytest.mark.parametrize(
    "addr",
    ["127.0.0.1:8000", "localhost:1", "my-host.example.com:65535"],
)
def test_valid_addresses(addr):
    validate_address(addr)


@pytest.mark.parametrize(
    "addr",
    [
        "http://127.0.0.1:8000",
        "127.0.0.1",
        "127.0.0.1:0",
        "127.0.0.1:99999",
        "127.0.0.1:port",
        ":8000",
        12345,
    ],
)
def test_invalid_addresses(addr):
    with pytest.raises(ValueError):
        validate_address(addr)


def test_validate_addresses_dict():
    validate_addresses({"alice": "127.0.0.1:1234", "bob": "127.0.0.1:1235"})
    with pytest.raises(ValueError):
        validate_addresses({})
    with pytest.raises(ValueError):
        validate_addresses({"alice": "nope"})
    with pytest.raises(ValueError):
        validate_addresses({"": "127.0.0.1:1234"})


def test_dict2tuple():
    assert dict2tuple({"b": 1, "a": 2}) == (("a", 2), ("b", 1))
    assert dict2tuple(None) == ()


def test_is_tpu_backend_is_true_for_tpu_alone(monkeypatch):
    import jax

    from rayfed_tpu.utils import is_tpu_backend

    for name in ("cpu", "gpu", "cuda", "rocm", "TPU", "tpu_plugin", ""):
        monkeypatch.setattr(jax, "default_backend", lambda name=name: name)
        assert not is_tpu_backend(), name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert is_tpu_backend()


def test_compilation_cache_goes_where_the_environment_says(monkeypatch):
    import jax

    from rayfed_tpu.utils import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    # Set from outside: that directory is returned and code sets no other
    # (jax itself reads the variable into its config at import).
    assert enable_compilation_cache() == "/somewhere/else"
    assert enable_compilation_cache(".jax_test_cache") == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_default_is_a_fixed_path_in_the_checkout(
    monkeypatch,
):
    import os

    import jax

    from rayfed_tpu.utils import enable_compilation_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compilation_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache"
        )
        # Same answer every time: nothing from tempfile, a pid or the clock.
        assert enable_compilation_cache() == enable_compilation_cache()
        assert enable_compilation_cache(".jax_test_cache") == os.path.join(
            repo, ".jax_test_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(repo, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".jax_cache/" in ignored and ".jax_test_cache/" in ignored


def test_party_mesh_refuses_another_platform():
    from rayfed_tpu.mesh import build_mesh

    # A party configured for the chip must not come up on the CPU that jax
    # falls back to when it cannot get the chip.
    with pytest.raises(RuntimeError, match=r"demands platform 'tpu'.*\['cpu'\]"):
        build_mesh(platform="tpu")
    mesh = build_mesh(platform="cpu")
    assert {d.platform for d in mesh.devices.flat} == {"cpu"}
