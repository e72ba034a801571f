"""Stage-0 front-end tests: lexer, ParamSet, API state machine, PLY.

Modeled on pbrt-v3's src/tests/parser.cpp tokenizer tests plus API-level
checks of the directive state machine (SURVEY.md §4).
"""

import os

import numpy as np
import pytest

from tpu_pbrt.scene.lexer import Tokenizer
from tpu_pbrt.scene.paramset import ParamSet
from tpu_pbrt.scene.api import pbrt_init, parse_string, Options
from tpu_pbrt.scene import plyreader
from tpu_pbrt.utils.error import PbrtError


def toks(s):
    return [(t.kind, t.value) for t in Tokenizer(s)]


class TestLexer:
    def test_basic(self):
        assert toks('Shape "sphere" "float radius" [2.5]') == [
            ("ident", "Shape"),
            ("string", "sphere"),
            ("string", "float radius"),
            ("lbrack", "["),
            ("number", 2.5),
            ("rbrack", "]"),
        ]

    def test_comments_and_negatives(self):
        out = toks("# a comment\nTranslate -1 2e3 .5 # trailing\nRotate 90 0 0 1")
        assert out[0] == ("ident", "Translate")
        assert out[1:4] == [("number", -1.0), ("number", 2000.0), ("number", 0.5)]
        assert out[4] == ("ident", "Rotate")

    def test_string_escapes(self):
        assert toks(r'"a\"b" "c\nd"') == [("string", 'a"b'), ("string", "c\nd")]

    def test_line_tracking(self):
        t = Tokenizer("A\nB\n  C")
        lines = [tok.line for tok in t]
        assert lines == [1, 2, 3]


class TestParamSet:
    def test_typed_lookups(self):
        ps = ParamSet()
        ps.add("float radius", [2.5])
        ps.add("integer nsamples", [16])
        ps.add("bool flag", ["true"])
        ps.add("string name", ["hello"])
        ps.add("point3 P", [0, 0, 0, 1, 0, 0, 0, 1, 0])
        ps.add("rgb Kd", [0.5, 0.25, 0.125])
        assert ps.find_one_float("radius", 1.0) == 2.5
        assert ps.find_one_float("missing", 7.0) == 7.0
        assert ps.find_one_int("nsamples", 4) == 16
        assert ps.find_one_bool("flag", False) is True
        assert ps.find_one_string("name", "") == "hello"
        assert ps.find_point3("P").shape == (3, 3)
        np.testing.assert_allclose(ps.find_one_spectrum("Kd", 0.0), [0.5, 0.25, 0.125])

    def test_blackbody_and_xyz(self):
        ps = ParamSet()
        ps.add("blackbody L", [6500, 1.0])
        rgb = ps.find_one_spectrum("L", 0.0)
        assert rgb.shape == (3,)
        assert np.all(rgb > 0)
        # ~6500K is roughly white: channels within ~25% of each other
        assert rgb.max() / rgb.min() < 1.4

    def test_spectrum_pairs(self):
        ps = ParamSet()
        # flat SPD == equal-energy white; y integral normalization -> ~[1,1,1]
        ps.add("spectrum L", [400, 1.0, 500, 1.0, 600, 1.0, 700, 1.0])
        rgb = ps.find_one_spectrum("L", 0.0)
        assert abs(rgb.sum() / 3 - 1.0) < 0.2


SIMPLE_SCENE = """
LookAt 0 0 -5  0 0 0  0 1 0
Camera "perspective" "float fov" [45]
Film "image" "integer xresolution" [64] "integer yresolution" [48]
Sampler "halton" "integer pixelsamples" [8]
Integrator "path" "integer maxdepth" [3]
WorldBegin
  LightSource "point" "point3 from" [0 5 0] "rgb I" [10 10 10]
  AttributeBegin
    Translate 0 0 2
    Material "matte" "rgb Kd" [0.8 0.2 0.2]
    Shape "sphere" "float radius" [1]
  AttributeEnd
  AttributeBegin
    AreaLightSource "diffuse" "rgb L" [5 5 5]
    Shape "trianglemesh"
      "integer indices" [0 1 2]
      "point3 P" [-1 4 0  1 4 0  0 4 1]
  AttributeEnd
WorldEnd
"""


class TestAPI:
    def test_simple_scene_state(self):
        api = parse_string(SIMPLE_SCENE)
        ro = api.last_render_options
        assert ro.camera_name == "perspective"
        assert ro.camera_params.find_one_float("fov", 90) == 45
        assert ro.film_params.find_one_int("xresolution", 0) == 64
        assert ro.integrator_name == "path"
        assert len(ro.shapes) == 2
        assert len(ro.lights) == 1
        sphere = ro.shapes[0]
        assert sphere.type == "sphere"
        assert sphere.material.type == "matte"
        np.testing.assert_allclose(sphere.material.params["Kd"][1], [0.8, 0.2, 0.2])
        # CTM: camera LookAt must not leak into world block
        np.testing.assert_allclose(sphere.object_to_world[0].apply_point([0, 0, 0]), [0, 0, 2])
        tri = ro.shapes[1]
        assert tri.area_light is not None
        np.testing.assert_allclose(tri.area_light.find_one_spectrum("L", 0), [5, 5, 5])

    def test_attribute_stack_restores(self):
        api = parse_string(
            """
            WorldBegin
            Material "mirror"
            AttributeBegin
              Material "glass"
              Translate 1 0 0
            AttributeEnd
            Shape "sphere"
            WorldEnd
            """
        )
        s = api.last_render_options.shapes[0]
        assert s.material.type == "mirror"
        assert s.object_to_world[0].is_identity()

    def test_named_materials(self):
        api = parse_string(
            """
            WorldBegin
            MakeNamedMaterial "red" "string type" "matte" "rgb Kd" [1 0 0]
            Material "glass"
            NamedMaterial "red"
            Shape "sphere"
            WorldEnd
            """
        )
        s = api.last_render_options.shapes[0]
        assert s.material.type == "matte"
        np.testing.assert_allclose(s.material.params["Kd"][1], [1, 0, 0])

    def test_object_instancing(self):
        api = parse_string(
            """
            WorldBegin
            ObjectBegin "tree"
              Shape "sphere" "float radius" [0.5]
            ObjectEnd
            Translate 5 0 0
            ObjectInstance "tree"
            Translate 5 0 0
            ObjectInstance "tree"
            WorldEnd
            """
        )
        ro = api.last_render_options
        assert len(ro.instances["tree"]) == 1
        assert len(ro.instance_uses) == 2
        np.testing.assert_allclose(ro.instance_uses[1].instance_to_world[0].apply_point([0, 0, 0]), [10, 0, 0])

    def test_texture_registration(self):
        api = parse_string(
            """
            WorldBegin
            Texture "checks" "spectrum" "checkerboard"
               "float uscale" [8] "float vscale" [8]
               "rgb tex1" [.1 .1 .1] "rgb tex2" [.8 .8 .8]
            Material "matte" "texture Kd" "checks"
            Shape "sphere"
            WorldEnd
            """
        )
        s = api.last_render_options.shapes[0]
        kd = s.material.params["Kd"]
        assert kd[0] == "checkerboard"
        assert kd[1]["mapping"]["su"] == 8

    def test_world_state_enforced(self):
        api = pbrt_init()
        with pytest.raises(PbrtError):
            parse_string('Shape "sphere"', api)

    def test_unmatched_attribute_end(self):
        with pytest.raises(PbrtError):
            parse_string("WorldBegin\nAttributeEnd\nWorldEnd")

    def test_reverse_orientation(self):
        api = parse_string(
            """
            WorldBegin
            ReverseOrientation
            Shape "sphere"
            WorldEnd
            """
        )
        assert api.last_render_options.shapes[0].reverse_orientation is True

    def test_transform_directive_column_major(self):
        api = parse_string(
            """
            WorldBegin
            Transform [1 0 0 0  0 1 0 0  0 0 1 0  3 4 5 1]
            Shape "sphere"
            WorldEnd
            """
        )
        s = api.last_render_options.shapes[0]
        np.testing.assert_allclose(s.object_to_world[0].apply_point([0, 0, 0]), [3, 4, 5])

    def test_include(self, tmp_path):
        inc = tmp_path / "inner.pbrt"
        inc.write_text('Material "matte" "rgb Kd" [0 1 0]\nShape "sphere"\n')
        main = tmp_path / "main.pbrt"
        main.write_text(f'WorldBegin\nInclude "inner.pbrt"\nWorldEnd\n')
        from tpu_pbrt.scene.api import parse_file

        api = parse_file(str(main))
        assert len(api.last_render_options.shapes) == 1
        np.testing.assert_allclose(api.last_render_options.shapes[0].material.params["Kd"][1], [0, 1, 0])

    def test_medium_interface(self):
        api = parse_string(
            """
            MakeNamedMedium "fog" "string type" "homogeneous" "rgb sigma_s" [1 1 1]
            WorldBegin
            MediumInterface "fog" ""
            Shape "sphere"
            WorldEnd
            """
        )
        s = api.last_render_options.shapes[0]
        assert s.inside_medium == "fog"
        assert s.outside_medium == ""
        assert "fog" in api.last_render_options.named_media


class TestPLY:
    def test_roundtrip_binary(self, tmp_path):
        v = np.array([[0, 0, 0], [1, 0, 0], [0, 1, 0], [1, 1, 0]], dtype=np.float64)
        f = np.array([[0, 1, 2], [1, 3, 2]], dtype=np.int64)
        n = np.tile([0.0, 0.0, 1.0], (4, 1))
        p = str(tmp_path / "quad.ply")
        plyreader.write_ply(p, v, f, n)
        m = plyreader.read_ply(p)
        np.testing.assert_allclose(m["vertices"], v)
        np.testing.assert_array_equal(m["indices"], f)
        np.testing.assert_allclose(m["normals"], n)

    def test_ascii_with_quad(self, tmp_path):
        txt = """ply
format ascii 1.0
element vertex 4
property float x
property float y
property float z
element face 1
property list uchar int vertex_indices
end_header
0 0 0
1 0 0
1 1 0
0 1 0
4 0 1 2 3
"""
        p = tmp_path / "quad.ply"
        p.write_text(txt)
        m = plyreader.read_ply(str(p))
        assert m["vertices"].shape == (4, 3)
        # quad fan-triangulated into 2 tris
        np.testing.assert_array_equal(m["indices"], [[0, 1, 2], [0, 2, 3]])

    def test_plymesh_reaches_the_compiled_scene(self, tmp_path):
        """`Shape "plymesh"` through the scene compiler (the shape every
        pbrt-v3-scenes mesh uses): the written killeroo-like file compiles
        to the same triangle count as the in-memory builder's mesh."""
        from tpu_pbrt.scene.api import Options, compile_file
        from tpu_pbrt.scenes import (
            compile_api, make_killeroo_like, write_killeroo_like,
        )

        kw = dict(res=8, spp=1, n_theta=6, n_phi=8)
        path = write_killeroo_like(str(tmp_path / "k.pbrt"), **kw)
        scene, _ = compile_file(path, Options(quiet=True))
        ref, _ = compile_api(make_killeroo_like(**kw))
        assert scene.dev["tri_verts"].shape == ref.dev["tri_verts"].shape
        # the same triangles (the PLY holds them in f32; order may differ)
        a = np.sort(np.asarray(scene.dev["tri_verts"]).reshape(-1))
        b = np.sort(np.asarray(ref.dev["tri_verts"]).reshape(-1))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
