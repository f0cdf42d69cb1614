"""Config-key & env-var registry closure (ISSUE 8 satellite).

Every ``ballista.*`` config-key literal and ``BALLISTA_*`` env read in
the package must resolve to a declared registry entry; docs/config.md is
generated from the registries and pinned here; the runtime
``warn_unknown_env`` catches the typo'd-knob case static analysis can't.
"""

import logging
import re

import pytest

from ballista_tpu import config as cfg
from ballista_tpu.analysis import configlint
from ballista_tpu.errors import ConfigError


def _rules(src: str):
    return [d.rule for d in configlint.lint_source(src)]


# ------------------------------------------------------------ tier-1 gate --


def test_tree_is_closed_over_the_registries():
    diags, summary = configlint.lint_tree()
    assert diags == [], "\n".join(str(d) for d in diags)
    # the scan saw real traffic (not vacuously green)
    m = re.match(r"(\d+) config-key literals \+ (\d+) env read", summary)
    assert m and int(m.group(1)) > 0 and int(m.group(2)) > 0, summary


def test_docs_config_md_is_pinned_to_the_registries():
    assert configlint.docs_path().exists(), (
        "docs/config.md missing — regenerate with "
        "`python -m ballista_tpu.analysis --write-config-docs`"
    )
    assert configlint.docs_path().read_text() == (
        configlint.render_config_docs()
    ), (
        "docs/config.md is stale vs config.py registries — regenerate "
        "with `python -m ballista_tpu.analysis --write-config-docs`"
    )


def test_generated_docs_cover_both_registries():
    text = configlint.render_config_docs()
    for name in cfg._entries():
        assert f"`{name}`" in text, name
    for e in cfg.ENV_REGISTRY:
        assert f"`{e.name}`" in text, e.name


def test_docs_config_md_lists_exactly_the_registry_keys():
    listed = re.findall(
        r"^\| `(ballista\.[a-z0-9_.]+)` \|",
        configlint.docs_path().read_text(),
        flags=re.M,
    )
    assert sorted(listed) == sorted(cfg._entries())
    assert len(listed) == len(set(listed))


@pytest.mark.parametrize(
    "key",
    [
        "ballista.tpu.shuffle_local_fastpath",  # removed, PR 31
        "ballista.tpu.join_expansion",  # removed, PR 31
        "ballista.tpu.shuffle_compresion",  # a misspelt key
    ],
)
def test_removed_and_misspelt_keys_are_unknown(key):
    with pytest.raises(ConfigError, match="unknown configuration key"):
        cfg.BallistaConfig().with_setting(key, "true")
    assert _rules(f'k = "{key}"\n') == ["unknown-config-key"]


# ----------------------------------------------------------- mutations --


def test_unknown_env_read_rejected_and_declared_accepted():
    assert _rules(
        'import os\nx = os.environ.get("BALLISTA_BOGUS_KNOB")\n'
    ) == ["unknown-env"]
    assert _rules(
        'import os\nx = os.environ.get("BALLISTA_TPU_PREWARM", "off")\n'
    ) == []
    # subscript + pop forms are covered too
    assert _rules(
        'import os\nx = os.environ["BALLISTA_NOPE"]\n'
    ) == ["unknown-env"]
    assert _rules(
        'import os\nos.environ.pop("BALLISTA_NOPE2", None)\n'
    ) == ["unknown-env"]


def test_fstring_env_reads_need_a_declared_prefix_family():
    assert _rules(
        "import os\n"
        "def f(name):\n"
        '    return os.environ.get(f"BALLISTA_SCHEDULER_{name}")\n'
    ) == []
    assert _rules(
        "import os\n"
        "def f(name):\n"
        '    return os.environ.get(f"BALLISTA_MYSTERY_{name}")\n'
    ) == ["unknown-env"]


def test_unknown_config_key_literal_rejected():
    assert _rules('k = "ballista.tpu.not_a_key"\n') == [
        "unknown-config-key"
    ]
    assert _rules('k = "ballista.tpu.prewarm"\n') == []
    # internal task props are declared by prefix
    assert _rules('k = "ballista.internal.task_attempt"\n') == []


# ------------------------------------------------------------- runtime --


def test_env_entry_for_exact_and_prefix():
    assert cfg.env_entry_for("BALLISTA_TPU_PREWARM").name == (
        "BALLISTA_TPU_PREWARM"
    )
    assert cfg.env_entry_for("BALLISTA_SCHEDULER_BIND_PORT").name == (
        "BALLISTA_SCHEDULER_*"
    )
    assert cfg.env_entry_for("BALLISTA_TYPO") is None


def test_warn_unknown_env_flags_typod_knobs(monkeypatch, caplog):
    monkeypatch.setenv("BALLISTA_PREWRAM", "on")  # the classic typo
    monkeypatch.setattr(cfg, "_ENV_WARNED", False)
    with caplog.at_level(logging.WARNING, logger="ballista_tpu.config"):
        unknown = cfg.warn_unknown_env()
    assert "BALLISTA_PREWRAM" in unknown
    assert any("BALLISTA_PREWRAM" in r.message for r in caplog.records)


def test_warn_unknown_env_clean_when_all_declared(monkeypatch):
    monkeypatch.delenv("BALLISTA_PREWRAM", raising=False)
    monkeypatch.setattr(cfg, "_ENV_WARNED", False)
    unknown = cfg.warn_unknown_env()
    assert unknown == [], unknown
