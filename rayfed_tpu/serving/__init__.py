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

"""Federated inference serving plane (docs/serving.md).

One party hosts the freshest aggregate and serves generate / beam /
speculative-decode requests under concurrent load while training rounds
keep landing new aggregates:

 - :mod:`rayfed_tpu.serving.server` — admission control (batched
   prefill, chunked prefill with a per-step token budget) + continuous
   (iteration-level) batching over the KV pool;
 - :mod:`rayfed_tpu.serving.kv_pool` — the KV store: the block-granular
   paged pool (block tables, on-demand grants, prefix reuse by table
   sharing);
 - :mod:`rayfed_tpu.serving.sampling` — the one sampler, traced at the
   end of the engine's programs: the next token is chosen on the device
   and ids, not logits, come back;
 - :mod:`rayfed_tpu.serving.publish` — versioned atomic hot model swap
   over device-resident snapshots;
 - :mod:`rayfed_tpu.serving.stream` — incremental token streaming over
   the inline lane;
 - :mod:`rayfed_tpu.serving.client` — ``fed.serve()`` /
   ``fed.submit_request()``: requests ride the small-message inline lane,
   model swaps ride the bulk/striped lane (and replicate to standbys).
"""

from rayfed_tpu.serving.client import (  # noqa: F401
    ServeHandle,
    serve,
    submit_request,
)
from rayfed_tpu.serving.kv_pool import PagedKVPool  # noqa: F401
from rayfed_tpu.serving.publish import ModelBank  # noqa: F401
from rayfed_tpu.serving.server import (  # noqa: F401
    InferenceServer,
    ServerOverloadedError,
    ServerStoppedError,
)
from rayfed_tpu.serving.stream import (  # noqa: F401
    LocalTokenStream,
    StreamConsumerError,
    TokenStream,
)

__all__ = [
    "serve",
    "submit_request",
    "ServeHandle",
    "InferenceServer",
    "PagedKVPool",
    "ModelBank",
    "LocalTokenStream",
    "TokenStream",
    "StreamConsumerError",
    "ServerOverloadedError",
    "ServerStoppedError",
]
