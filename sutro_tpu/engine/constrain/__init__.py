"""Schema-constrained decoding: JSON schema -> byte NFA -> token masks.

See SURVEY §2.3 ("Structured output") and §7.3. Public surface:
``schema_constraint_factory(schema, tokenizer)`` returning a per-row
``TokenFSM`` factory, built from scratch; the engine keeps one a
(schema, tokenizer) in its ``FactoryTable`` (``eng.constraint_factories``)
and every site that serves a schema asks that. Wired into jobs by
engine/api.py when ``output_schema`` is set, and into sampling via the
``allowed`` mask.
"""

from .fsm import (  # noqa: F401
    ConstraintFactory,
    FactoryTable,
    MaskCache,
    TokenFSM,
    TokenTable,
    schema_constraint_factory,
)
from .schema import compile_schema  # noqa: F401
