"""AOT policy-application serving (docs/SERVING.md; README "Serving a
found policy"; docs/RESILIENCE.md "Serving under overload").

The searched policies are only useful if traffic can hit them: this
package turns a ``final_policy.json`` into a batch-coalescing
augmentation service backed by ahead-of-time-compiled executables over
a small set of padded batch shapes — dispatch-only execution in the
Anakin style (PAPERS.md: *Podracer architectures for scalable RL*),
with every compile paid at load time through the compile seam
(``core/compilecache.py``) — and keeps it standing under overload:
fail-fast admission control, deadline-aware shedding, adaptive-LIFO
draining, circuit breaking, graceful drain, and hot policy reload.
"""

from fast_autoaugment_tpu.serve.policy_server import (
    AotPolicyApplier,
    CircuitOpenError,
    DeadlineExpiredError,
    PolicyServer,
    ServeError,
    ServerOverloadedError,
    ServerStoppedError,
)

__all__ = [
    "AotPolicyApplier",
    "CircuitOpenError",
    "DeadlineExpiredError",
    "PolicyServer",
    "ServeError",
    "ServerOverloadedError",
    "ServerStoppedError",
]
