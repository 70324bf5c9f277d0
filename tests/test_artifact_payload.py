"""The artifact's payload is written leaf by leaf, in place: the file
still holds exactly the packed wire format, for every learner."""
import jax
import pytest

from repro.core.serialization import serialize
from repro.serve import load_artifact, save_artifact
from test_serve import HPARAMS, _small_ensemble


@pytest.mark.parametrize("committee", [None, 3])
@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_payload_is_the_packed_wire_format(name, committee, tmp_path):
    _, spec, ens, _ = _small_ensemble(name, jax.random.PRNGKey(1), committee_size=committee)
    path = save_artifact(tmp_path / "a.mafl", spec, ens, committee_size=committee)
    art = load_artifact(path)
    want = serialize(ens, packed=True)[0]
    assert path.read_bytes().endswith(want)
    assert art.manifest["payload_bytes"] == len(want)
    assert serialize(art.ensemble, packed=True)[0] == want
