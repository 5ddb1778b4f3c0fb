"""ktpulint — the repo's contract linter (ref: the reference enforces its
conventions with hack/verify-* static checks and go vet passes).

This package encodes the contracts that previously lived only as prose
in CHANGES.md — injectable clocks everywhere, no silently swallowed
errors, seeded randomness, metric naming discipline, no silent caps,
and a cycle-free lock order — as named AST rules over stdlib `ast`
(no third-party dependencies, no kubernetes_tpu import: the walk must
stay cheap enough for tier-1).

Run it:

    python -m tools.ktpulint                # full tree (kubernetes_tpu/)
    python -m tools.ktpulint --changed      # only files touched vs main
    python -m tools.ktpulint path/to/file.py

Rules:

    KTPU001 swallowed-exception   broad except whose body only drops
    KTPU002 wall-clock            direct time.time/sleep, datetime.now
    KTPU003 unseeded-randomness   module-level random.* / np.random.*
    KTPU004 metric-naming         _total/_seconds suffixes + resolution
    KTPU005 silent-cap            *_CAP/*_LIMIT clamp with no counter
    KTPU006 lock-order            acquires-while-holding cycles
    KTPU007 environment-read      os.environ / os.getenv outside cmd/

Suppress inline (reason MANDATORY — a bare disable is itself an error):

    except Exception:  # ktpulint: disable=KTPU001 <why this is fine>

Grandfathered findings live in baseline.json; its counts may only
shrink (tests/test_static_analysis.py enforces both directions).
"""

from .engine import (Finding, Module, lint_modules, lint_text,  # noqa: F401
                     load_baseline, load_modules, render_report)
from .rules import ALL_RULES, RULE_INDEX  # noqa: F401
