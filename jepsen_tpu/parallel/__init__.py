"""Device-mesh parallelism for the checker.

The reference scales linearizability checking by sharding *keys*
(jepsen.independent splits one multi-key history into per-key subhistories
checked via bounded-pmap, independent.clj:285) and by racing search
strategies (knossos.competition). Here the key axis becomes a vmap batch
dimension sharded over a ``jax.sharding.Mesh`` (SURVEY.md section 5
"Distributed communication backend").
"""

from .keyshard import (check_batch_encoded,  # noqa: F401
                       check_batch_histories, keys_mesh)
from .searchshard import (check_encoded_sharded,  # noqa: F401
                          check_history_sharded)
