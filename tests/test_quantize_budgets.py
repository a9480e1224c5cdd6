"""The declared accuracy budgets of the low-precision serving path (ISSUE 8,
acceptance criterion), apart from `test_quantize.py`: the report runs all four
zoo models eagerly under every policy, which is a worker's work for a minute."""

from deeplearning4j_tpu.models.zoo import PRECISION_ERROR_BUDGETS
from deeplearning4j_tpu.optimize import quantize


def test_error_budgets_hold_on_all_four_zoo_models():
    """bf16 and int8 stay within the budgets declared in
    `zoo.PRECISION_ERROR_BUDGETS` for LeNet, char-LSTM, charTransformer,
    and the deep autoencoder (small variants; CPU-deterministic)."""
    report = quantize.error_budget_report(small=True)
    assert set(report) == set(PRECISION_ERROR_BUDGETS)
    for model, by_policy in report.items():
        for policy, row in by_policy.items():
            assert row["within_budget"], (model, policy, row)
