"""Resolution engine (port of the reference's lib/server.js logic)."""
from binder_tpu.resolver.engine import (  # noqa: F401
    DEFAULT_TTL,
    AnswerPlan,
    Resolver,
    SERVICE_CHILD_TYPES,
)
