"""The least-bytes functions against row counts worked by hand."""

import traffic

ROWS = {"lineitem": 5_999_785, "orders": 1_500_000, "customer": 150_000}


def test_least_bytes():
    t = traffic.load_templates(["q1", "q6", "q3"])
    # q1: 4 float64, 1 date32, 2 dictionary codes = 44 B a row
    assert t["q1"].least_bytes(ROWS) == 44 * 5_999_785 == 263_990_540
    # q6: 3 float64, 1 date32 = 28 B a row
    assert t["q6"].least_bytes(ROWS) == 28 * 5_999_785 == 167_993_980
    # q3: lineitem 28 B, orders 24 B, customer 12 B a row
    assert t["q3"].least_bytes(ROWS) == (
        167_993_980 + 36_000_000 + 1_800_000
    )
