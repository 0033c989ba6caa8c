"""What every described architecture answers (``models/arch.py``): each
trait :class:`~ddstore_tpu.models.arch.Arch` declares, as a field of the
family's tuple or as the one value the family fixes; and a description may
repeat what its family fixes but not change it. Tuples alone, no model is
initialised."""

import json
import os

import pytest

from ddstore_tpu.models import arch as A

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a benchmark configuration of each family, by the tuple it describes
FAMILIES = {"glm47-flash-ep8": A.MlaMoeArch, "lfm2-8b-a1b-ep4": A.Lfm2MoeArch,
            "nemotron3-nano-ep16": A.NemotronHArch,
            "sdar-30b-a3b-ep8": A.SdarMoeArch,
            "smallthinker-21b-a3b-ep4": A.SmallThinkerArch}


def described(name):
    """The configuration ``name``'s description at its dry-run sizes."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg.get("dry_run", {})}


def another(value):
    """A value of a trait's type other than ``value``."""
    if isinstance(value, bool):
        return not value
    if value is None:
        return 1
    return value + 1 if isinstance(value, (int, float)) else value + "_x"


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_every_arch_answers_every_trait(name):
    model = A.lm_from_description(described(name))
    a = model.arch
    assert type(a) is FAMILIES[name] and isinstance(a, A.Arch)
    fixed = type(a).fixed()
    # each trait a field or fixed, never both
    assert set(fixed) == set(A.TRAITS) - set(a._fields)
    for trait in A.TRAITS:
        want = a._asdict()[trait] if trait in a._fields else fixed[trait]
        assert getattr(a, trait) == want, trait
    for layer in range(model.layers):
        rotary, window = a.attention(layer)
        assert isinstance(rotary, bool) and (window is None or window > 0)
    # the traits are no part of the tuple: what the references are handed
    assert list(a._asdict()) == list(a._fields)
    assert a._replace(expert_share=(0, 1))._fields == a._fields


CASES = [(name, trait) for name, cls in sorted(FAMILIES.items())
         for trait in cls.fixed()]


@pytest.mark.parametrize("name,trait", CASES,
                         ids=[f"{n.split('-')[0]}-{t}" for n, t in CASES])
def test_a_description_may_repeat_what_its_family_fixes_not_change_it(
        name, trait):
    desc, want = described(name), FAMILIES[name].fixed()[trait]
    assert A.lm_from_description(dict(desc, **{trait: want})).arch \
        == A.lm_from_description(desc).arch
    other = another(want)
    with pytest.raises(ValueError) as e:
        A.lm_from_description(dict(desc, **{trait: other}))
    assert str(e.value) == (f"{trait}={other!r} is not built here "
                            f"(only {trait}={want!r})")
