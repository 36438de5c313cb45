"""Slow-marker audit: tier-1 (`pytest -x -q`, which filters `-m "not slow"`)
must stay under ~5 minutes, so every test module must make an explicit
choice — carry a module-level ``pytestmark = pytest.mark.slow`` or be listed
in ``TIER1_MODULES`` below. A new module that does neither fails here,
forcing the author to budget it deliberately instead of silently growing
the tier-1 wall clock."""
import re
from pathlib import Path

TESTS_DIR = Path(__file__).parent

# modules vetted to run in tier-1 (keep the combined suite < ~5 min)
TIER1_MODULES = {
    "test_adversary",
    "test_affinity",
    "test_auction",
    "test_auction_dense",
    "test_auction_pallas",
    "test_chip_compile",
    "test_churn_storm",
    "test_column_market",
    "test_dag_workload",
    "test_dispatch",
    "test_docs",
    "test_engine_decode",
    "test_exploration",
    "test_federation",
    "test_hoeffding",
    "test_hoeffding_batch",
    "test_hub_sharding",
    "test_marker_audit",
    "test_mcmf",
    "test_mechanism",
    "test_mla",
    "test_models",
    "test_moe",
    "test_placement",
    "test_predictor_batch",
    "test_reputation_identity",
    "test_routing_fused",
    "test_run_workload",
    "test_served_deepseek",
    "test_sharding",
    "test_simulator",
    "test_system",
    "test_tracing",
    "test_truthfulness",
}

SLOW_RE = re.compile(r"^pytestmark\s*=.*pytest\.mark\.slow", re.MULTILINE)


def test_every_module_is_budgeted():
    unbudgeted = []
    for path in sorted(TESTS_DIR.glob("test_*.py")):
        name = path.stem
        if name in TIER1_MODULES:
            continue
        if SLOW_RE.search(path.read_text()):
            continue
        unbudgeted.append(name)
    assert not unbudgeted, (
        f"modules {unbudgeted} are neither slow-marked nor vetted for "
        f"tier-1; add `pytestmark = pytest.mark.slow` or (if genuinely "
        f"fast) list them in TIER1_MODULES")


def test_vetted_list_is_current():
    """No stale entries: every vetted module still exists."""
    existing = {p.stem for p in TESTS_DIR.glob("test_*.py")}
    stale = TIER1_MODULES - existing
    assert not stale, f"TIER1_MODULES lists removed modules: {stale}"
